"""The trace reduction: interval arithmetic on a small synthetic trace,
and span extraction from a small recorded one."""

import jax
import jax.numpy as jnp

import trace_reduce as tr


def test_busy_gaps_and_names():
    dev = {"/device:TPU:0": [("fusion.1", 10, 20), ("fusion.2", 15, 30),
                             ("copy", 50, 60)]}
    spans = [("call", 0, 100), ("fleet.engine", 5, 35),
             ("evaluator.build", 35, 50)]
    out = tr.reduce_events(dev, spans)
    assert out["window_s"] == 100e-9
    assert out["busy_s"] == 30e-9               # [10, 30) and [50, 60)
    assert out["device_ops"][0] == ["fusion.2", 15e-9]
    gaps = dict((round(s * 1e9), n) for n, s in out["idle_gaps"])
    assert gaps[40] == "call"                   # [60, 100)
    assert gaps[20] == "evaluator.build"        # [30, 50), midpoint 40
    assert gaps[10] == "fleet.engine"           # [0, 10), midpoint 5
    assert tr.reduce_events({"/device:TPU:0": []}, spans) is None


def test_union_of_overlaps():
    assert tr.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.gaps_ns([(2, 4), (3, 6)], 0, 10) == [(0, 2), (6, 10)]


def test_recorded_trace_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    path = next(tmp_path.rglob("*.xplane.pb"))
    dev, spans = tr.read_xplane(str(path), "bench.")
    assert [s[0] for s in spans] == ["call", "call"]
    assert all(e > s for _, s, e in spans)
    assert dev == {}                            # the CPU has no TPU plane
    assert tr.reduce_trace_dir(str(tmp_path), "bench.") is None
