"""The benchmark harness: one closed-loop caller, one cell per process.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  The harness finds everything by name:

* ``bench/configs/<config>.json`` (the file named in ``configs``): the
  deployment, with its source, cuts and guarantees;
* ``bench/traffic/<traffic>.json``: every parameter of the traffic, and
  under ``driver`` the name of the general driver that reads them;
* ``bench/drivers/<driver>.py``: builds the system under test from the
  two files and the seed, makes one call of it, and checks what the
  calls produced against ``bench/reference``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run sets up (imports, engine, the recording the traffic needs, one
warm-up call that compiles), then calls the driver back to back for
``--seconds``.  The window runs from the first call's start to the last
call's end.  With ``--trace 1`` the calls run with the program's
profiler sections on (they block inside each section), and after the
window a few more calls run under the JAX profiler for the device
numbers.  After the window the driver compares what the calls produced
with the plain reference; the last stdout line is the JSON result.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import re
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN_PREFIX = "bench."
_T_IMPORT = time.time()


def process_start() -> float:
    """Wall-clock time at which this process started (from /proc; the
    harness's import time where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - started)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: pathlib.Path = ROOT):
    """(benchmark, cell, configuration, traffic) for one cell name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``<checkout>/.jax_cache``.  Every
    program is kept, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span_profiler(trace: bool):
    """The program's ``repro.obs.Profiler``, whose sections also land in
    a JAX profiler trace as ``bench.<name>`` annotations."""
    import jax
    from repro.obs import Profiler

    class SpanProfiler(Profiler):
        @contextlib.contextmanager
        def section(self, name: str):
            ann = (jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
                   if trace else contextlib.nullcontext())
            with ann, super().section(name) as prof:
                yield prof

    return SpanProfiler()


def compile_count() -> int:
    """Backend compilations so far in this process."""
    from repro.obs.profile import COMPILE_LOG

    return int(COMPILE_LOG.counts["compile_s"])


def run_calls(driver, start: int, seconds: float, profiler,
              max_calls: Optional[int] = None) -> Dict:
    """Call the driver back to back from call index ``start`` until
    ``seconds`` have passed (or ``max_calls`` calls were made)."""
    calls: List[Dict] = []
    failed = 0
    w0 = time.perf_counter()
    i = start
    while True:
        t0 = time.perf_counter()
        try:
            out = driver.call(i, profiler)
        except Exception:        # a failed call counts; the run goes on
            failed += 1
            traceback.print_exc(file=sys.stderr)
            out = {"real_ops": 0, "cells": 0}
        t1 = time.perf_counter()
        calls.append({"index": i, "start": t0, "end": t1, **out})
        i += 1
        if (len(calls) >= max_calls if max_calls
                else t1 - w0 >= seconds):
            break
    return {"calls": calls, "failed": failed,
            "window_s": calls[-1]["end"] - calls[0]["start"]}


def end_to_end(name: str, window: Dict, setup_s: float) -> float:
    """The value of one end-to-end metric over the window."""
    import numpy as np

    calls = window["calls"]
    if name == "setup_s":
        return setup_s
    if name == "sim_ops_per_s":
        return sum(c["real_ops"] for c in calls) / window["window_s"]
    m = re.fullmatch(r"call_p(\d+)_s", name)
    if m:
        return float(np.percentile([c["end"] - c["start"] for c in calls],
                                   int(m.group(1))))
    raise KeyError(f"no end-to-end metric {name!r}")


def applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def device_info(devices) -> Dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def trace_segment(driver, start: int, n_calls: int) -> Optional[Dict]:
    """``n_calls`` calls under the JAX profiler, reduced to the device
    numbers (see :mod:`trace_reduce`)."""
    import jax
    from trace_reduce import reduce_trace_dir

    prof = span_profiler(trace=True)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            seg = run_calls(driver, start, 0.0, prof, max_calls=n_calls)
        t0 = time.perf_counter()
        out = reduce_trace_dir(d, SPAN_PREFIX)
    if out is not None:
        out["real_ops"] = sum(c["real_ops"] for c in seg["calls"])
        out["reduce_s"] = time.perf_counter() - t0
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_chip: bool = True, driver_hook=None,
         root: pathlib.Path = ROOT) -> int:
    """One run of one cell.  ``require_chip=False`` and ``driver_hook``
    (called with the driver after set-up) are for the CPU rehearsals
    under ``bench/tests``; a benchmark run passes neither."""
    t_start = process_start()
    args = parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload, root)
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)

    t_imports = time.time()
    import jax

    t_import_jax = time.time()
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    devices = devices[: cell["chips"]]
    cache = use_compile_cache()

    t_jax = time.time()
    driver = load_module("drivers", traffic["driver"]).Driver(
        config, traffic, args.seed)
    driver.setup()
    if driver_hook is not None:
        driver_hook(driver)
    t_warm = time.time()
    driver.call(-1, None)               # warm-up: compiles every shape
    setup_s = time.time() - t_start
    print(json.dumps({"setup_parts_s": {
        "start_to_main": t_imports - t_start,
        "import_jax": t_import_jax - t_imports,
        "jax_devices": t_jax - t_import_jax,
        "driver_setup": t_warm - t_jax,
        "warm_up_call": t_start + setup_s - t_warm}}), file=sys.stderr)

    prof = span_profiler(trace=False) if args.trace else None
    c0 = compile_count()
    window = run_calls(driver, 0, args.seconds, prof)
    compiles = compile_count() - c0
    device = device_info(devices)

    result: Dict = {"correct": False, "attempted": len(window["calls"]),
                    "failed": window["failed"]}
    metrics: Dict = {}
    if args.trace:
        seg = trace_segment(driver, len(window["calls"]),
                            int(traffic.get("trace_calls", 1)))
        ctx = {"sections": {n: s["wall_s"]
                            for n, s in prof.sections.items()},
               "real_ops": sum(c["real_ops"] for c in window["calls"]),
               "cells": sum(c["cells"] for c in window["calls"]),
               "compiles": compiles, "trace": seg}
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                v = load_module("metrics", m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if seg is not None:
            device["busy_s"] = seg["busy_s"]
            device["window_s"] = seg["window_s"]
            result["breakdown"] = {"device_ops": seg["device_ops"],
                                   "idle_gaps": seg["idle_gaps"]}
            print(json.dumps({"trace": {k: v for k, v in seg.items()
                                        if k not in ("device_ops",
                                                     "idle_gaps")}}),
                  file=sys.stderr)
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {
                    "value": end_to_end(m["name"], window, setup_s),
                    "unit": m["unit"]}

    print(json.dumps({"compile_cache": cache, "setup_s": setup_s,
                      "window_s": window["window_s"],
                      "window_compiles": compiles,
                      "real_ops_per_call": [c["real_ops"]
                                            for c in window["calls"]],
                      "call_s": [round(c["end"] - c["start"], 4)
                                 for c in window["calls"]]}),
          file=sys.stderr)
    t0 = time.perf_counter()
    checks = driver.check()
    print(json.dumps({"check_s": time.perf_counter() - t0}),
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result["correct"] = (window["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    print(json.dumps(result))
    return 0
