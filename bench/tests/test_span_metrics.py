"""The per-layer metrics that read the program's host spans
(``build.lanes``, ``build.dyn``, ``replay.prepare``, ``fleet.check``,
``fleet.rollup``): a traced run of each cell at the tiny geometry on
the CPU reports each of them in the cells its ``workloads`` names, and
only there, each above zero, and the run stays correct."""

import json

import pytest

import harness

SPAN_METRICS = ("build_lanes_us_per_op", "build_dyn_us_per_op",
                "prepare_us_per_op", "check_us_per_op", "rollup_us_per_op")


@pytest.mark.parametrize("workload", ("array4.grid96", "zenfs.kvbench"))
def test_traced_run_reports_the_span_metrics(tiny_root, capsys, workload):
    rc = harness.main(["--workload", workload, "--seed", "2718281829",
                       "--seconds", "1", "--trace", "1"],
                      require_chip=False, root=tiny_root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    want = {n for n in SPAN_METRICS if workload in entries[n]["workloads"]}
    got = {n for n in res["metrics"] if n in SPAN_METRICS}
    assert got == want
    assert all(res["metrics"][n]["value"] > 0 for n in got)
