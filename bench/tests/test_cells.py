"""Each cell's whole run at a tiny geometry on the CPU: set-up, warm-up,
window, the result line, and the comparison with the plain reference,
with the harness's look for a chip skipped."""

import json

import pytest

import harness

CELLS = ("array4.grid96", "zenfs.kvbench")


def _run(root, capsys, workload, trace=0, hook=None, seconds=1):
    rc = harness.main(["--workload", workload, "--seed", "3000000017",
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_chip=False, driver_hook=hook, root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(tiny_root, capsys, workload):
    res, err = _run(tiny_root, capsys, workload)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if harness.applies(m, workload)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["limit"] == 0 for c in res["checks"].values())
    assert err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_the_host_layers(tiny_root, capsys):
    res, _ = _run(tiny_root, capsys, "zenfs.kvbench", trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    assert {"record_us_per_op", "engine_us_per_op", "timing_us_per_op",
            "host_other_us_per_op", "pad_share",
            "window_compiles"} <= set(m)
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["pad_share"]["value"] < 100
    # no TPU plane in a CPU trace: the device metrics stay out
    assert "device_idle_share" not in m and "breakdown" not in res


def test_refuses_without_a_chip(tiny_root, capsys):
    rc = harness.main(["--workload", "zenfs.kvbench", "--seed", "1",
                       "--seconds", "1"], root=tiny_root)
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "Nothing was run" in err


def test_every_metric_has_a_reader():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for w in bench["workloads"]:
        _, _, config, traffic = harness.load_cell(w["name"])
        harness.load_module("drivers", traffic["driver"])


def test_a_sampled_cell_is_data_only(tmp_path, capsys):
    """``array4.random8`` (Open questions in PERF.md) needs a traffic
    file and a ``BENCHMARK.json`` entry, and no code."""
    import tiny

    root = tiny.make_root(tmp_path)
    t = json.loads((root / "bench/traffic/grid96.json").read_text())
    t["configs_per_call"] = 8
    (root / "bench/traffic/random8.json").write_text(json.dumps(t))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "array4.random8",
                               "config": "zn540-array4",
                               "traffic": "random8", "chips": 1,
                               "why": "8 sampled configs per call"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res, err = _run(root, capsys, "array4.random8")
    assert res["correct"] is True and res["attempted"] >= 2
    assert res["checks"]["no_rows_compared"]["value"] == 0
