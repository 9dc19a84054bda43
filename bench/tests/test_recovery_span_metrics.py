"""The per-layer metrics that read the member rebuild's program spans
(``build.rebuild``, ``fleet.recover``): a traced run of the rebuild
cell at the tiny geometry on the CPU reports both, above zero, and
stays correct; no other cell lists them."""

import json

import pytest

import harness

METRICS = ("rebuild_us_per_op", "recover_us_per_op")


@pytest.fixture(scope="module")
def root(tiny_root):
    path = tiny_root / "bench" / "traffic" / "rebuild24.json"
    t = json.loads(path.read_text())
    t["grid"]["segments"], t["grid"]["chunks"] = [4, 2], [32, 64]
    t.pop("pad_ops", None)
    path.write_text(json.dumps(t))
    return tiny_root


def test_traced_run_reports_the_recovery_spans(root, capsys):
    rc = harness.main(["--workload", "array4.rebuild24", "--seed",
                       "2718281831",
                       "--seconds", "0.5", "--trace", "1"],
                      require_chip=False, root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(METRICS) <= set(res["metrics"])
    assert all(res["metrics"][n]["value"] > 0 for n in METRICS)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == ["array4.rebuild24"]
