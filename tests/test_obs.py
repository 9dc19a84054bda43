"""Flight-recorder tests: telemetry purity, decoding, export, profiling.

The load-bearing property is **effect-freeness**: running the same
program with and without an ``ObsConfig`` must produce bit-identical
``DeviceState`` and ``OpTrace`` (the recorder only *reads* the integer
state machine).  On top of that:

* histogram totals reconcile exactly with the end-state counters;
* tenant / zone / fleet decoders agree with brute-force re-aggregation
  of the materialized trace;
* ``op_stream`` page-stream reconstruction from the ``OpTrace`` is
  bit-identical to the legacy device's ``trace=True`` streams across
  element specs (the timing model consumes these streams, so drift here
  silently corrupts latency numbers);
* the Perfetto export validates against the checked-in JSON schema
  (subset validator always; real ``jsonschema`` when installed);
* the profiler / recompile counter read real jit caches: a new shape
  compiles, a repeat does not, and repeated same-shape ``Evaluator``
  generations keep a flat cache (the ``pad_quantum`` guarantee);
* ``span`` finds the current profiler, counts a nested same-name span
  once, reaches a ``section`` override, adds no device sync, and the
  two-pass fleet builder returns the one-pass builder's arrays.
"""

import contextlib
import dataclasses
import json
import importlib.util
import pathlib
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core.device_legacy import LegacyZNSDevice
from repro.core.elements import BLOCK, FIXED, SUPERBLOCK, hchunk, vchunk
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.obs import (ObsConfig, Profiler, RecompileCounter,
                       device_rollup, fleet_timelines, jit_cache_size,
                       lane_timeline, span, tenant_timelines,
                       validate_trace, zone_timelines)

REPO = pathlib.Path(__file__).resolve().parent.parent
SPECS = [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK, FIXED]


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1,
                         blocks_per_lun=8, pages_per_block=4,
                         page_bytes=4096)


def tiny_engine(spec, max_active=3, **kw):
    return E.ZoneEngine(tiny_flash(), ZoneGeometry(4, 2), spec,
                        max_active=max_active, **kw)


#: the fuzz row mirrors test_engine_diff: overflow writes mix with
#: legal fills, FINISH exercises dummy pages, RESET exercises erases
_FUZZ_ROW = st.tuples(
    st.sampled_from([E.OP_WRITE, E.OP_FINISH, E.OP_RESET]),
    st.integers(0, 3),
    st.integers(1, 34),
    st.booleans(),
)


def _mixed_program(eng, n=24, seed=0):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        op = [E.OP_WRITE, E.OP_FINISH, E.OP_RESET][int(rng.integers(3))]
        rows.append((op, int(rng.integers(4)),
                     int(rng.integers(1, eng.cfg.zone_pages + 3)),
                     E.F_HOST if rng.integers(2) else 0))
    return E.encode_program(rows)


# --------------------------------------------------------------------- #
# effect-freeness: telemetry-on == telemetry-off, bit for bit
# --------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(st.integers(0, len(SPECS) - 1),
       st.lists(_FUZZ_ROW, min_size=1, max_size=40))
def test_telemetry_is_effect_free(spec_i, rows):
    eng = tiny_engine(SPECS[spec_i])
    prog = E.encode_program(
        [(op, z, n, E.F_HOST if host else 0)
         for op, z, n, host in rows])
    s0 = eng.init_state()
    state_off, trace_off = eng.run(s0, prog)
    state_on, trace_on, tel = eng.run(
        s0, prog, obs=ObsConfig(n_buckets=7))
    for a, b in zip(state_off, state_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(trace_off, trace_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(tel.step) == len(prog)


def test_batched_telemetry_is_effect_free():
    eng = tiny_engine(SUPERBLOCK)
    progs = np.stack([_mixed_program(eng, seed=s) for s in range(3)])
    s0 = eng.init_state()
    state_off, trace_off = eng.run_batch(s0, progs)
    state_on, trace_on, tel = eng.run_batch(
        s0, progs, obs=ObsConfig(n_buckets=5))
    for a, b in zip(state_off, state_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(trace_off, trace_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(tel.host).shape == (3, 5)


# --------------------------------------------------------------------- #
# histogram reconciliation: bucket sums == end-state counters
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("spec", [BLOCK, SUPERBLOCK, FIXED],
                         ids=lambda s: s.name)
def test_histogram_totals_match_end_state(spec):
    eng = tiny_engine(spec)
    prog = _mixed_program(eng, n=30, seed=3)
    obs = ObsConfig(n_buckets=4)
    state, trace, tel = eng.run(eng.init_state(), prog, obs=obs)
    tl = lane_timeline(obs, tel)
    assert sum(tl["host"]) == int(state.host_pages)
    assert sum(tl["dummy"]) == int(state.dummy_pages)
    assert sum(tl["erases"]) == int(state.block_erases)
    assert sum(tl["allocs"]) == int(state.alloc_calls)
    ok = np.asarray(trace.ok)
    assert sum(tl["ok_ops"]) == int(ok.sum())
    assert sum(tl["illegal_ops"]) == len(prog) - int(ok.sum())
    # cumulative dlwa's last point is the device's end-state DLWA
    h, d = int(state.host_pages), int(state.dummy_pages)
    want = (h + d) / h if h else 1.0
    assert tl["dlwa"][-1] == pytest.approx(want)
    # gauges bound the series they track
    assert max(tl["active_max"]) <= eng.cfg.max_active
    assert max(tl["wear_max"]) <= int(np.asarray(state.elem_wear).max())


def test_bucket_binning_is_progress_ordered():
    """Op i of n lands in bucket i*B//n: an all-host-write program puts
    its pages in op order, so per-bucket host counts must follow the
    program's page schedule exactly."""
    eng = tiny_engine(SUPERBLOCK)
    rows = [(E.OP_WRITE, z, 2, E.F_HOST) for z in (0, 1, 2)] * 4
    prog = E.encode_program(rows)
    obs = ObsConfig(n_buckets=3)
    _, trace, tel = eng.run(eng.init_state(), prog, obs=obs)
    host = np.asarray(trace.host_delta, dtype=np.int64)
    want = [0, 0, 0]
    for i in range(len(prog)):
        want[min(i * 3 // len(prog), 2)] += int(host[i])
    assert np.asarray(tel.host).tolist() == want


def test_tenant_binning_width5():
    eng = tiny_engine(SUPERBLOCK)
    rows = np.array([
        [E.OP_WRITE, 0, 3, E.F_HOST, 0],
        [E.OP_WRITE, 1, 5, E.F_HOST, 1],
        [E.OP_WRITE, 0, 2, E.F_HOST, 0],
        [E.OP_FINISH, 1, 0, 0, 7],       # out-of-range tag clips to 2
    ], dtype=np.int32)
    obs = ObsConfig(n_buckets=2, n_tenants=3)
    state, trace, tel = eng.run(eng.init_state(), rows, obs=obs)
    th = np.asarray(tel.tenant_host).sum(axis=0)
    td = np.asarray(tel.tenant_dummy).sum(axis=0)
    assert th.tolist() == [5, 5, 0]
    assert td.sum() == int(state.dummy_pages)
    assert td[0] == td[1] == 0           # FINISH pad went to class 2
    tls = tenant_timelines(obs, tel)
    assert sorted(tls) == [0, 1, 2]
    assert sum(tls[1]["host"]) == 5


# --------------------------------------------------------------------- #
# decoders: lane / fleet / rollup / zone
# --------------------------------------------------------------------- #
def test_fleet_timelines_and_rollup():
    eng = tiny_engine(SUPERBLOCK)
    progs = np.stack([_mixed_program(eng, seed=s) for s in range(4)])
    obs = ObsConfig(n_buckets=6)
    states, traces, tel = eng.run_batch(eng.init_state(), progs,
                                        obs=obs)
    with pytest.raises(ValueError, match="lane"):
        lane_timeline(obs, tel)          # batched needs explicit lane
    tls = fleet_timelines(obs, tel)
    assert len(tls) == 4
    host = np.asarray(states.host_pages)
    for lane, tl in enumerate(tls):
        assert sum(tl["host"]) == int(host[lane])
    pooled = device_rollup(tls)
    assert sum(pooled["host"]) == int(host.sum())
    for i in range(6):
        assert pooled["wear_max"][i] == max(
            tl["wear_max"][i] for tl in tls)
    assert device_rollup([]) == {}


def test_zone_timelines_match_trace():
    eng = tiny_engine(SUPERBLOCK)
    prog = _mixed_program(eng, n=30, seed=5)
    _, trace = eng.run(eng.init_state(), prog)
    per_zone = zone_timelines(prog, trace, n_buckets=5)
    zone = np.asarray(trace.zone)
    host = np.asarray(trace.host_delta, dtype=np.int64)
    wp = np.asarray(trace.wp_after, dtype=np.int64)
    assert sorted(per_zone) == sorted(
        {int(z) for z in np.asarray(prog)[:, 1]})
    for z, tl in per_zone.items():
        mask = zone == z
        assert sum(tl["host"]) == int(host[mask].sum())
        # wp gauge ends at the zone's last traced write pointer
        last = np.nonzero(mask)[0][-1]
        assert tl["wp"][-1] == int(wp[last])
        assert all(v >= 0 for v in tl["wp"])  # carried, never sentinel


def test_obsconfig_rejects_degenerate_shapes():
    for kw in ({"n_buckets": 0}, {"n_tenants": 0}, {"n_buckets": -3}):
        with pytest.raises(ValueError):
            ObsConfig(**kw)


# --------------------------------------------------------------------- #
# op_stream: OpTrace -> page-stream reconstruction vs legacy trace=True
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_op_stream_reconstruction_matches_legacy(spec):
    eng = tiny_engine(spec, max_active=3)
    leg = LegacyZNSDevice(tiny_flash(), ZoneGeometry(4, 2), spec,
                          max_active=3)
    rows = []
    for z in range(3):
        rows.append((E.OP_WRITE, z, 3 + 2 * z, E.F_HOST))
        rows.append((E.OP_FINISH, z, 0, 0))
    prog = E.encode_program(rows)
    _, trace = eng.run(eng.init_state(), prog)
    ops = np.asarray(prog)
    for i, (op, z, n, _f) in enumerate(ops):
        if op == E.OP_WRITE:
            legacy = leg.zone_write(int(z), int(n), trace=True)
        else:
            legacy = leg.zone_finish(int(z), trace=True)
        mine = eng.op_stream(
            int(op), int(np.asarray(trace.wp_before)[i]),
            int(np.asarray(trace.wp_after)[i]),
            int(np.asarray(trace.dummy_delta)[i]),
            np.asarray(trace.elems)[i], np.asarray(trace.cols)[i])
        assert (mine is None) == (legacy is None), (spec.name, i)
        if mine is None:
            continue
        luns, channels, kind = mine
        assert np.array_equal(luns, legacy.luns), (spec.name, i)
        assert np.array_equal(channels, legacy.channels), (spec.name, i)
        assert kind == "write"


# --------------------------------------------------------------------- #
# Perfetto export + schema validation
# --------------------------------------------------------------------- #
def _tiny_fleet(n_configs=2, n_devices=2):
    from repro.fleet import (N_TENANTS, build_fleet_batch, grid_space,
                             run_fleet)
    flash = FlashGeometry(n_channels=4, ways_per_channel=2,
                          blocks_per_lun=64, pages_per_block=16,
                          page_bytes=4096)
    eng = E.ZoneEngine(flash, ZoneGeometry(8, 4), SUPERBLOCK,
                       max_active=6)
    configs = grid_space(segments=(4,), chunks=(64,),
                         parities=(False, True),
                         wear=(True,))[:n_configs]
    programs, dyn, _ = build_fleet_batch(eng, configs,
                                         n_devices=n_devices)
    obs = ObsConfig(n_buckets=8, n_tenants=N_TENANTS + 1)
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                    obs=obs)
    return eng, configs, res, obs


def test_trace_export_validates_and_loads(tmp_path):
    from repro.obs import fleet_trace_events, write_trace
    eng, _configs, res, _obs = _tiny_fleet()
    events = fleet_trace_events(res, eng)
    phases = {e["ph"] for e in events}
    assert phases == {"M", "X", "C"}
    # every lane got a named process track; tenants are named threads
    names = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in names} == {"process_name", "thread_name"}
    # durations follow the service-time model: ceil(pages/P) * t_page
    t_page = (eng.flash.t_prog + eng.flash.t_xfer) * 1e6
    for e in events:
        if e["ph"] == "X" and e["args"]["pages"]:
            pg = e["args"]["pages"]
            want = -(-pg // int(eng.cfg.parallelism)) * t_page
            assert e["dur"] == pytest.approx(want, rel=1e-6)
            assert e["ts"] >= -1e-9
    obj = write_trace(tmp_path / "t_trace.json", events,
                      meta={"run": "test"})
    validate_trace(obj)                  # mini + jsonschema when present
    back = json.loads((tmp_path / "t_trace.json").read_text())
    assert back["otherData"] == {"run": "test"}
    assert len(back["traceEvents"]) == len(events)


def test_trace_validation_rejects_malformed():
    ok = {"traceEvents": [
        {"ph": "X", "name": "WRITE z0", "pid": 0, "ts": 0.0,
         "dur": 1.0}]}
    validate_trace(ok)
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"displayTimeUnit": "ms"})
    with pytest.raises(ValueError, match="ph"):
        validate_trace({"traceEvents": [{"name": "x", "pid": 0}]})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"ph": "Q", "name": "x", "pid": 0}]})   # ph outside enum
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 0, "ts": "late"}]})


def test_fleet_metrics_registry():
    from repro.obs.export import fleet_metrics
    eng, _configs, res, _obs = _tiny_fleet()
    m = fleet_metrics(res, eng).as_dict()
    real = np.asarray(res.programs)[:, :, 0] != 0
    assert m["counters"]["ops_ok"] + m["counters"]["ops_illegal"] \
        == int(real.sum())
    host = np.asarray(res.host_delta, dtype=np.int64).sum()
    assert m["counters"]["host_pages"] + m["counters"]["parity_pages"] \
        == int(host)
    assert m["gauges"]["makespan_s"] == pytest.approx(
        float(np.asarray(res.makespans).max()))
    assert any(k.startswith("tenant") and k.endswith("_p99_latency_s")
               for k in m["gauges"])


# --------------------------------------------------------------------- #
# the --obs acceptance path: emit_fleet_obs via fleet_search
# --------------------------------------------------------------------- #
def _load_fleet_search():
    spec = importlib.util.spec_from_file_location(
        "fleet_search", REPO / "benchmarks" / "fleet_search.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_emit_obs_artifacts_end_to_end(tmp_path):
    fs = _load_fleet_search()
    eng, configs, _res, _obs = _tiny_fleet()
    out = fs.emit_obs_artifacts(
        eng, configs, n_devices=2,
        out_prefix=str(tmp_path / "t"), n_buckets=8,
        meta={"suite": "test"})
    trace = json.loads(pathlib.Path(out["trace"]).read_text())
    validate_trace(trace)
    assert out["n_events"] == len(trace["traceEvents"]) > 0
    obs = json.loads(pathlib.Path(out["obs"]).read_text())
    assert obs["schema_version"] == 1
    assert obs["meta"]["suite"] == "test"
    assert len(obs["lane_labels"]) == len(obs["timelines"]["lanes"]) \
        == len(configs) * 2
    assert set(obs["jit_cache"]) == {
        "apply_op", "run_program", "run_programs",
        "simulate_fleet_ops"}
    assert "fleet.engine" in obs["profile"]
    # the two DLWA views reconcile through the same three counters:
    # the registry gauge is the paper's (parity pages count as
    # amplification), the pooled timeline's is device-level (the
    # in-scan recorder sees parity traffic as host-flagged writes)
    c = obs["metrics"]["counters"]
    h, p, d = (c["host_pages"], c["parity_pages"],
               c["superfluous_pages"])
    assert obs["metrics"]["gauges"]["dlwa"] == pytest.approx(
        (h + p + d) / h)
    assert obs["timelines"]["fleet"]["dlwa"][-1] == pytest.approx(
        (h + p + d) / (h + p))


def test_emit_fleet_obs_requires_telemetry(tmp_path):
    from repro.fleet import N_TENANTS, build_fleet_batch, run_fleet
    from repro.obs import emit_fleet_obs
    eng, configs, res, obs = _tiny_fleet()
    bare = run_fleet(
        eng, *build_fleet_batch(eng, configs, n_devices=2)[:1],
        n_tenants=N_TENANTS)
    with pytest.raises(ValueError, match="telemetry"):
        emit_fleet_obs(bare, eng, obs=obs,
                       out_prefix=str(tmp_path / "x"))


def test_obs_report_renders_sections(tmp_path):
    fs = _load_fleet_search()
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    eng, configs, _res, _obs = _tiny_fleet()
    out = fs.emit_obs_artifacts(eng, configs, n_devices=2,
                                out_prefix=str(tmp_path / "r"),
                                n_buckets=8)
    obs = json.loads(pathlib.Path(out["obs"]).read_text())
    report = obs_report.render(obs, max_lanes=2)
    for section in ("# Flight-recorder report", "## DLWA vs time",
                    "## Wear frontier vs time",
                    "## p99 latency per tenant class",
                    "## Recompile table", "## Dispatch profile"):
        assert section in report, section
    assert "lanes omitted" in report     # 4 lanes, max_lanes=2
    assert obs_report.spark([0, 1, 2, 3]) == "▁▃▅█"
    assert obs_report.spark([5, 5]) == "▁▁"


# --------------------------------------------------------------------- #
# profiling: sections, recompile counting, Evaluator stability
# --------------------------------------------------------------------- #
def test_profiler_sections_accumulate():
    prof = Profiler()
    with prof.section("a"):
        pass
    with prof.section("a"):
        with prof.section("b"):
            pass
    snap = prof.snapshot()
    assert snap["a"]["calls"] == 2.0
    assert snap["b"]["calls"] == 1.0
    assert snap["a"]["wall_s"] >= snap["a"]["execute_s"] >= 0.0
    snap["a"]["calls"] = 99.0            # snapshot is a copy
    assert prof.sections["a"]["calls"] == 2.0


def test_recompile_counter_sees_new_shapes():
    # run_program's jit cache is process-global, so the engine config
    # (12 blocks per LUN) is one no other test builds: a file that ran
    # earlier on the same worker cannot have compiled these shapes
    eng = E.ZoneEngine(
        FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=12,
                      pages_per_block=4, page_bytes=4096),
        ZoneGeometry(4, 2), SUPERBLOCK, max_active=3)
    rc = RecompileCounter(run_program=E.run_program)
    assert jit_cache_size(E.run_program) >= 0
    p1 = _mixed_program(eng, n=10)
    eng.run(eng.init_state(), p1)
    base = rc.counts()
    eng.run(eng.init_state(), _mixed_program(eng, n=10, seed=9))
    assert rc.delta(base)["run_program"] == 0    # same shape: cache hit
    eng.run(eng.init_state(), _mixed_program(eng, n=11))
    assert rc.delta(base)["run_program"] == 1    # new shape: one entry
    with pytest.raises(ValueError):
        RecompileCounter()


def test_evaluator_jit_cache_stable_across_generations():
    """The acceptance property: repeated same-shape Evaluator
    generations must not grow the run_programs cache (pad_quantum keeps
    the batch rectangular and shape-stable)."""
    from repro.fleet import Evaluator, grid_space
    eng, _configs, _res, _obs = _tiny_fleet()
    configs = grid_space(segments=(4,), chunks=(64,),
                         parities=(False, True),
                         wear=(True, False))[:4]
    ev = Evaluator(eng, n_devices=2, profiler=Profiler())
    counts = []
    for _ in range(3):
        rows = ev.evaluate(configs)
        assert len(rows) == len(configs)
        counts.append(ev.jit_cache()["run_programs"])
    assert counts[0] == counts[1] == counts[2]
    assert ev.profiler.sections["evaluator.build"]["calls"] == 3.0
    assert ev.profiler.sections["fleet.engine"]["calls"] == 3.0


def test_evolve_history_carries_profile_when_instrumented():
    from repro.fleet import (Evaluator, EvolveParams, SearchSpace,
                             evolve)
    eng, _c, _r, _o = _tiny_fleet()
    space = SearchSpace(segments=(4,), chunks=(64,),
                        parities=(False, True))
    params = EvolveParams(population=2, generations=2)
    plain = evolve(eng, space=space, params=params, seed=0,
                   n_devices=2)
    assert all("jit_cache" not in row for row in plain.history)
    ev = Evaluator(eng, n_devices=2, profiler=Profiler())
    inst = evolve(eng, space=space, params=params, seed=0,
                  n_devices=2, evaluator=ev)
    assert inst.history, "instrumented evolve produced no generations"
    for row in inst.history:
        assert row["jit_cache"]["run_programs"] >= 1
        assert "fleet.engine" in row["profile"]
    # instrumentation must not change what the search found
    assert [r["best_so_far"] for r in inst.history] == \
        [r["best_so_far"] for r in plain.history]


# --------------------------------------------------------------------- #
# spans: the current profiler, the same-name rule, the library's spans
# --------------------------------------------------------------------- #
def test_span_without_a_profiler_is_a_shared_noop():
    idle = Profiler()
    a, b = span("a"), span("b")
    assert a is b                        # shared: nothing allocated
    with a:
        with span("c"):
            pass
    assert idle.sections == {}
    assert span("a", None) is a


@pytest.mark.parametrize("raises", [False, True])
def test_section_makes_its_profiler_current_until_exit(raises):
    outer, inner = Profiler(), Profiler()
    with (pytest.raises(RuntimeError) if raises
          else contextlib.nullcontext()):
        with outer.section("call"):
            with span("a"):
                pass
            with inner.section("call"):
                with span("b"):
                    pass
            with span("c"):          # the outer one is current again
                if raises:
                    raise RuntimeError("inside a span")
    assert set(outer.sections) == {"call", "a", "c"}
    assert set(inner.sections) == {"call", "b"}
    with span("after"):
        pass
    assert "after" not in outer.sections | inner.sections
    with outer.section("again"):     # the same-name rule was reset too
        with span("c"):
            pass
    assert outer.sections["c"]["calls"] == 2.0


@pytest.mark.parametrize("explicit", [False, True])
def test_nested_span_with_the_same_name_counts_once(explicit):
    prof = Profiler()
    with prof.section("call"):
        with span("fleet.rollup", prof if explicit else None):
            with span("fleet.rollup"):
                with span("fleet.rollup", prof):
                    pass
        with span("fleet.rollup"):
            pass
    assert prof.sections["fleet.rollup"]["calls"] == 2.0
    assert prof.sections["call"]["calls"] == 1.0


def test_a_section_override_receives_every_span():
    seen = []

    class Annotating(Profiler):
        @contextlib.contextmanager
        def section(self, name):
            seen.append(name)
            with super().section(name) as prof:
                yield prof

    prof = Annotating()
    with prof.section("call"):
        with span("a"):
            with span("a"):          # open already: not entered
                pass
        with span("b", prof):
            pass
    with span("c", prof):
        with span("d"):              # current inside its own section
            pass
    assert seen == ["call", "a", "b", "c", "d"]


def test_evaluator_spans_build_check_and_rollups():
    from repro.fleet import Evaluator, grid_space
    eng, _c, _r, _o = _tiny_fleet()
    configs = grid_space(segments=(4,), chunks=(64,),
                         parities=(False, True), wear=(True,))
    prof = Profiler()
    Evaluator(eng, n_devices=2, profiler=prof).evaluate(configs)
    sec = prof.sections
    assert {"evaluator.build", "build.lanes", "build.dyn",
            "fleet.engine", "fleet.timing", "fleet.decode",
            "fleet.check", "fleet.rollup"} <= set(sec)
    # one check and one rollup span a dispatch, however many configs
    assert sec["fleet.check"]["calls"] == sec["fleet.rollup"]["calls"] \
        == 1.0
    assert (sec["build.lanes"]["wall_s"] + sec["build.dyn"]["wall_s"]
            <= sec["evaluator.build"]["wall_s"])


def test_replay_and_lane_metrics_span_prepare_check_and_rollups():
    from repro import storage as S
    eng = tiny_engine(SUPERBLOCK)
    rec = S.RecordingBackend(eng.flash, zone_pages=eng.cfg.zone_pages,
                             n_zones=4, max_active=3)
    S.record_cache(rec, n_accesses=60, n_keys=12, seed=0,
                   capacity_zones=4, obj_pages=2)
    prof = Profiler()
    with prof.section("call"):
        res = S.replay_recorders(eng, [rec, rec],
                                 dyns=[eng.dyn(), eng.dyn()],
                                 pad_quantum=32, profiler=prof)
        for lane in range(2):
            S.lane_metrics(eng, res, lane)
            res.pooled_wear(eng, np.asarray([lane]))
        res.tenant_class_report()
    sec = prof.sections
    assert {"replay.prepare", "fleet.check", "fleet.rollup",
            "fleet.engine"} <= set(sec)
    assert sec["fleet.check"]["calls"] == 1.0
    assert sec["fleet.rollup"]["calls"] == 5.0
    assert sec["replay.prepare"]["wall_s"] <= sec["call"]["wall_s"]


@pytest.mark.parametrize("mode", ["none", "given", "current"])
def test_only_the_timed_engine_and_timing_sections_block(monkeypatch,
                                                         mode):
    import jax
    from repro.fleet import N_TENANTS, Evaluator, build_fleet_batch, \
        grid_space, run_fleet
    eng, configs, _r, _o = _tiny_fleet()
    programs, dyn, _ = build_fleet_batch(eng, configs, n_devices=2)
    run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS)   # compile
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    prof = Profiler() if mode != "none" else None
    if mode == "current":
        with prof.section("call"):
            run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS)
    else:
        run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                  profiler=prof)
    # a timed run blocks in fleet.engine and fleet.timing, nothing more
    assert len(calls) == (0 if prof is None else 2)
    # and the build, check and rollup spans of a whole evaluation add
    # no block of their own
    del calls[:]
    ev = Evaluator(eng, n_devices=2, profiler=prof)
    ev.evaluate(grid_space(segments=(4,), chunks=(64,),
                           parities=(False, True), wear=(True,)))
    assert len(calls) == (0 if prof is None else 2)
    if prof is not None:
        assert {"build.dyn", "fleet.check", "fleet.rollup"} <= set(
            prof.sections)


def _one_pass_build(eng, configs, *, n_devices, fidelity=1.0,
                    pad_quantum=1):
    """The fleet builder as one loop over configs, lanes and dyns
    together: the arrays the two-pass builder has to return."""
    import math
    from repro.core.engine import stack_dyn
    from repro.fleet import search
    from repro.fleet.tenants import (interleave_tenants, pad_programs,
                                     stripe_program, tag_tenant)
    seg_pages = eng.zone_geom.parallelism * eng.flash.pages_per_block
    nd_max = search._nd_max(configs, n_devices)
    lane_programs, dyns, merged_per_config = [], [], []
    for fc in configs:
        specs_mix = fc.specs_mix()
        nd = fc.n_devices or n_devices
        member_zp = seg_pages * fc.n_segments
        cap = (nd - (1 if fc.parity else 0)) * member_zp
        merged = interleave_tenants(
            [tag_tenant(p, t)
             for t, p in enumerate(search.MIXES[fc.mix](eng, cap))])
        if fidelity < 1.0:
            merged = merged[: max(1, math.ceil(fidelity * len(merged)))]
        merged_per_config.append(merged)
        lane_programs += stripe_program(
            merged, n_devices=nd, chunk_pages=fc.chunk_pages,
            parity=fc.parity, member_zone_pages=member_zp,
            parity_tenant=search.N_TENANTS)
        dyns += [eng.dyn(spec=specs_mix[d % len(specs_mix)],
                         zone_pages=member_zp, wear_aware=fc.wear_aware,
                         alloc_policy=fc.alloc_policy)
                 for d in range(nd)]
        lane_programs += [np.zeros((0, 5), dtype=np.int32)] * (nd_max - nd)
        dyns += [eng.dyn()] * (nd_max - nd)
    q = max(1, pad_quantum)
    n_ops = -(-max((len(p) for p in lane_programs), default=0) // q) * q
    return (pad_programs(lane_programs, n_ops=n_ops), stack_dyn(dyns),
            merged_per_config)


@pytest.mark.parametrize("fidelity,pad_quantum", [(1.0, 1), (0.5, 64)])
def test_build_fleet_batch_returns_the_one_pass_arrays(fidelity,
                                                      pad_quantum):
    from repro.fleet import FleetConfig, build_fleet_batch, grid_space
    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=16, pages_per_block=4,
                          page_bytes=4096)
    eng = E.ZoneEngine(flash, ZoneGeometry(4, 4),
                       (SUPERBLOCK, BLOCK, vchunk(2)), max_active=6)
    configs = grid_space(segments=(4, 2), chunks=(8,),
                         parities=(False, True), wear=(True, False),
                         specs=(SUPERBLOCK, BLOCK, vchunk(2)),
                         policies=("traditional", "silent"))[::5]
    # a narrower array squares up with pad lanes; a spec-mix array
    configs += [FleetConfig("dlwa_pair", 4, 8, True, True,
                            (BLOCK, vchunk(2)), n_devices=2)]
    got = build_fleet_batch(eng, configs, n_devices=3, fidelity=fidelity,
                            pad_quantum=pad_quantum)
    want = _one_pass_build(eng, configs, n_devices=3, fidelity=fidelity,
                           pad_quantum=pad_quantum)
    np.testing.assert_array_equal(got[0], want[0])
    for f in E.DynConfig._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got[1], f)),
                                      np.asarray(getattr(want[1], f)), f)
    assert len(got[2]) == len(want[2]) == len(configs)
    for g, w in zip(got[2], want[2]):
        np.testing.assert_array_equal(g, w)


def test_build_fleet_batch_validates_every_config_before_building(
        monkeypatch):
    from repro.fleet import FleetConfig, build_fleet_batch, search
    built = []
    monkeypatch.setitem(search.MIXES, "counted",
                        lambda eng, cap: built.append(cap) or [])
    eng = tiny_engine(SUPERBLOCK)
    good = FleetConfig("counted", 2, 8, False, True)
    with pytest.raises(ValueError, match="not a member"):
        build_fleet_batch(eng, [good, dataclasses.replace(
            good, spec=BLOCK)], n_devices=2)
    with pytest.raises(ValueError, match="n_segments exceeds"):
        build_fleet_batch(eng, [good, dataclasses.replace(
            good, n_segments=99)], n_devices=2)
    assert built == []
