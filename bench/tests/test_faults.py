"""``correct`` has to come out false for the control and for each fault
a cell can have: the timed path is broken underneath a whole run (the
harness's look for a chip skipped) and the comparison must refuse it.
The cells run on one chip, so there is no exchange between chips to
leave out."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import harness

CELLS = ("array4.grid96", "zenfs.kvbench")


def _state_unchanged(eng):
    run_batch = eng.run_batch

    def broken(state, programs, dyn=None, **kw):
        out = run_batch(state, programs, dyn, **kw)
        init = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (len(programs),) + x.shape), state)
        return (init,) + tuple(out[1:])
    return broken


def _half_batch(eng):
    run_batch = eng.run_batch

    def broken(state, programs, dyn=None, **kw):
        half = np.array(programs)
        half[len(half) // 2:] = 0          # the second half never runs
        return run_batch(state, half, dyn, **kw)
    return broken


def _answer_altered(eng):
    run_batch = eng.run_batch

    def broken(state, programs, dyn=None, **kw):
        out = run_batch(state, programs, dyn, **kw)
        trace = out[1]
        trace = trace._replace(host_delta=trace.host_delta.at[0, 0].add(1))
        return (out[0], trace) + tuple(out[2:])
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(tiny_root, capsys, workload, fault):
    def hook(driver):
        driver.eng.run_batch = fault(driver.eng)

    rc = harness.main(["--workload", workload, "--seed", "3000000023",
                       "--seconds", "0.5"], require_chip=False,
                      driver_hook=hook, root=tiny_root)
    out, err = capsys.readouterr()
    if rc == 0:
        assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    else:                                  # a fault may also stop the run
        assert out == ""


@pytest.mark.parametrize("workload", CELLS)
def test_rows_dropped_where_built_is_not_correct(tiny_root, capsys,
                                                 monkeypatch, workload):
    """The op rows lose their last row where they are made: in the fleet
    builder's striping (grid) or in the recorder (kvbench)."""
    from repro.fleet import search
    from repro.storage.compile import RecordingBackend

    stripe, program = search.stripe_program, RecordingBackend.program
    monkeypatch.setattr(search, "stripe_program", lambda *a, **kw: [
        p[:-1] if d == 0 else p for d, p in enumerate(stripe(*a, **kw))])
    if workload == "zenfs.kvbench":
        monkeypatch.setattr(RecordingBackend, "program",
                            lambda self: program(self)[:-1])
    rc = harness.main(["--workload", workload, "--seed", "3000000029",
                       "--seconds", "0.5"], require_chip=False,
                      root=tiny_root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["op_rows"]["value"] > 0
    if workload == "array4.grid96":
        assert res["checks"]["real_ops"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, capsys, workload):
    assert control.main(["--workload", workload, "--seeds", "5,6,7",
                         "--seconds", "0.5"], require_chip=False,
                        root=tiny_root) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    for line in lines:
        assert all(v == 0 for v in line["program"].values()), line
        assert line["control"]["clock_bits"] > 0
        assert line["control"]["rows"] > 0
