"""A tiny-geometry copy of the benchmark for CPU rehearsals: the cells,
metrics and drivers of ``BENCHMARK.json`` over a 4-LUN device of
64 16-page blocks per LUN (16 zones of 4 segments), with KVBench runs
sized to fit it."""

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

FLASH = {"n_channels": 4, "ways_per_channel": 1, "blocks_per_lun": 64,
         "pages_per_block": 16, "page_bytes": 16384, "t_prog": 0.0007,
         "t_read": 6e-05, "t_erase": 0.0035, "t_xfer": 2.5e-05}
ZONE = {"parallelism": 4, "n_segments": 4}


def _kv(n_ops, memtable, chunk, jobs, ratio, levels):
    return {"n_ops": n_ops, "entry_bytes": 512, "memtable_entries": memtable,
            "size_ratio": ratio, "max_levels": levels,
            "max_concurrent_jobs": jobs, "io_chunk_pages": chunk,
            "dedup_fraction": 0.25, "update_overlap": 0.15}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """Write the tiny BENCHMARK.json, configs and traffic under ``tmp``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["flash"], cfg["zone"] = dict(FLASH), dict(ZONE)
        if "tenants" in cfg:
            cfg["tenants"]["window_zones"] = 8
            cfg["kvbench"] = _kv(4000, 512, 32, 4, 4, 4)
        else:
            cfg["zenfs"]["zones"] = 16
            cfg["kvbench"] = _kv(30000, 4096, 64, 4, 4, 4)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        if t["driver"] == "fleet":
            t["grid"]["segments"], t["grid"]["chunks"] = [4, 2], [32, 64]
        else:
            t["pad_ops"] = 256
        t["trace_calls"] = 1
        (tmp / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
