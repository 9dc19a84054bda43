"""The member-failure cell ``array4.rebuild24`` and the sampled cell
``array4.random8``, whole runs at the tiny geometry on the CPU: each is
correct, a traced run reports every per-layer metric that lists it and
compiles nothing in its window, and the rebuild cell's comparison
refuses the control, a rebuild that skips a stripe and a rebuild timed
without the failure's order."""

import json

import pytest

import control
import harness

NEW_CELLS = ("array4.rebuild24", "array4.random8")


@pytest.fixture(scope="session")
def root(tiny_root):
    """The tiny root with the new cells' grids cut to the tiny geometry
    (the tiny copy cuts only the ``fleet`` driver's grids)."""
    for name in ("rebuild24", "random8"):
        path = tiny_root / "bench" / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t["grid"]["segments"], t["grid"]["chunks"] = [4, 2], [32, 64]
        t.pop("pad_ops", None)
        path.write_text(json.dumps(t))
    return tiny_root


def _run(root, capsys, workload, seed, hook=None, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      require_chip=False, driver_hook=hook, root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", NEW_CELLS)
def test_new_cell_runs_and_is_correct(root, capsys, workload):
    drivers = []
    res = _run(root, capsys, workload, 3000000019, hook=drivers.append)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"sim_ops_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in res["checks"].values())
    _, configs, fleet_res = drivers[0].kept
    tags = fleet_res.programs[:, :, 4]
    if workload == "array4.rebuild24":
        assert len(configs) == 24
        assert all(fc.failure == (3, 0.75) for fc in configs)
        assert (tags == 3).sum() > 0          # rebuild rows were scanned
    else:
        assert len(configs) == 8 and fleet_res.programs.shape[0] == 32
        assert (tags == 3).sum() == 0


@pytest.mark.parametrize("workload", NEW_CELLS)
def test_traced_new_cell_reports_every_listed_metric(root, capsys,
                                                     workload):
    drivers = []
    res = _run(root, capsys, workload, 2147483659, hook=drivers.append,
               trace=1)
    assert res["correct"] is True
    bench = json.loads((root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if harness.applies(m, workload)}
    assert listed and listed <= set(res["metrics"])
    assert res["metrics"]["window_compiles"]["value"] == 0
    if workload == "array4.random8":
        # draws reach more than one scan length, all warmed in set-up
        assert len(drivers[0].warm_lengths) > 1


def test_rebuild_control_is_not_correct(root, capsys):
    assert control.main(["--workload", "array4.rebuild24", "--seeds",
                         "5,6", "--seconds", "0.5"], require_chip=False,
                        root=root) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2
    for line in lines:
        assert all(v == 0 for v in line["program"].values()), line
        assert line["control"]["clock_bits"] > 0
        assert line["control"]["rows"] > 0


def test_rebuild_that_skips_a_stripe_is_not_correct(root, capsys,
                                                     monkeypatch):
    """The builder's rebuild plan loses the first chunk row it would
    reconstruct: its survivor reads and the replacement's append."""
    from repro.fleet import tenants

    plan = tenants.plan_rebuild

    def skipping(*args, **kw):
        steps = plan(*args, **kw)
        first = [i for i, s in enumerate(steps) if s[0] == args[1]][:1]
        return steps[first[0] + 1:] if first else steps

    monkeypatch.setattr(tenants, "plan_rebuild", skipping)
    res = _run(root, capsys, "array4.rebuild24", 3000000031)
    assert res["correct"] is False
    assert res["checks"]["op_rows"]["value"] > 0
    assert res["checks"]["real_ops"]["value"] > 0


def test_rebuild_timed_without_the_failure_order_is_not_correct(
        root, capsys, monkeypatch):
    """The replacement's rows timed from 0 on its own clock, as if the
    failure had no instant and a rebuilt chunk no survivor reads."""
    from repro.core import timing
    from repro.fleet import runner

    def unordered(cols, pages, tenants, t_page, n_luns, rebuilds):
        return timing.simulate_fleet_ops(cols, pages, tenants, t_page,
                                         n_luns, rebuilds.tenant + 1)

    monkeypatch.setattr(runner, "_rebuild_clock", unordered)
    res = _run(root, capsys, "array4.rebuild24", 3000000037)
    assert res["correct"] is False
    assert res["checks"]["clock_bits"]["value"] > 0
    assert res["checks"]["rows"]["value"] > 0
    assert res["checks"]["op_rows"]["value"] == 0


def test_sampled_rows_compare_at_each_calls_own_length(tmp_path, capsys):
    """The sampled driver's draws pad to several lengths, and a row's
    ``ops_ok`` counts its padding: rows of calls at another length than
    the kept call's are compared with the reference at their own length
    (the ``fleet`` driver's comparison alone refuses them).  Two configs
    a call and no rounding make lengths vary at the tiny size."""
    import tiny

    root = tiny.make_root(tmp_path)
    path = root / "bench" / "traffic" / "random8.json"
    t = json.loads(path.read_text())
    t["grid"]["segments"], t["grid"]["chunks"] = [4, 2], [32, 64]
    t["configs_per_call"], t["pad_quantum"] = 2, 1
    path.write_text(json.dumps(t))
    drivers = []
    rc = harness.main(["--workload", "array4.random8", "--seed",
                       "3000000041", "--seconds", "4", "--trace", "0"],
                      require_chip=False, driver_hook=drivers.append,
                      root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    drv = drivers[0]
    n_kept = drv.kept[2].programs.shape[1]
    kept = {fc.describe() for fc in drv.kept[1]}
    assert any(n != n_kept and kept & {fc.describe() for fc in fcs}
               for (fcs, _), n in zip(drv.rows, drv.lengths))
    assert drv.other_lengths(n_kept) == 0
    base = type(drv).__mro__[1]               # the fleet driver
    assert base.check(drv)["rows"]["value"] > 0
