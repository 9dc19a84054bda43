"""Perf-trajectory tracker: per-op legacy pipelines vs batched engine.

Two tracked trajectories, each written as a JSON artifact:

* ``BENCH_zoneengine.json`` -- the DLWA occupancy sweep and the
  interference benchmark through the ``LegacyZNSDevice`` per-op loop vs
  the scan-compiled ``repro.core.engine`` op programs (PR 2's gate:
  dlwa sweep >= 5x).
  Since PR 6 the sweep runs as ONE padded ``run_programs`` dispatch
  (``workloads.interference_sweep_engine``); the artifact asserts the
  dispatch/compile count is flat across repeats (the recompile leak
  that had regressed it to 0.96x) and gates >= 1x.
* ``BENCH_fleet.json`` -- the 32-config fleet allocator sweep
  (``repro.fleet``) through one batched ``run_programs`` + one batched
  op-granular timing dispatch vs the per-config legacy pipeline
  (``ZNSArray`` over stateful-Python members + page-granular
  ``run_fleet_trace``, the ``benchmarks/raid_zns.py`` way) -- PR 3's
  gate: fleet sweep >= 5x.  Since PR 4 the artifact also carries an
  ``evolve`` section: the adaptive searcher's dispatched budget to
  reach the best objective of a 32-config random search
  (``repro.fleet.evolve.evolve_vs_random``; gate: target reached with
  <= half the random baseline's full-fidelity-equivalent evals).
  Since PR 5 a ``mixed_spec`` section times a SUPERBLOCK+BLOCK+VCHUNK2
  sweep through ONE union-config dispatch (per-lane ``DynConfig`` spec
  selection) vs the per-config legacy pipeline, whose members are
  built with each config's actual element spec -- the mixed-spec DLWA
  agreement is asserted before timing.
  Since PR 7 the legacy legs of the fleet sweep are timed once at a
  reduced config count and linearly scaled (the per-op pipeline is
  per-config sequential; the exactness assert still covers every
  config, and the measured/scaled split is recorded in the section
  and in ``meta``), and an ``array`` section times the engine-native
  ZNS-RAID data plane (``repro.array.ArrayEngine``: striping + parity
  + rebuild compiled into ONE batched dispatch) vs the object
  ``ZNSArray`` replay -- gate: >= 5x, with every per-array report
  asserted bit-identical to the object oracle first -- plus a
  rebuild-storm subsection asserted recompile-stable across repeated
  same-shape dispatches.
  Since PR 9 a ``trace`` section records real application traffic
  (ZoneFS/LSM compactions, checkpoint bursts, a Zipfian flash cache)
  through the :class:`repro.storage.RecordingBackend` trace compiler
  and replays the compiled op programs through ONE batched dispatch vs
  the identical op streams through the per-op legacy device -- gate:
  >= 5x with zero recompiles across repeated same-shape dispatches,
  after asserting per-lane DLWA agreement.

* ``BENCH_paper.json`` -- the paper's three headline claims as
  SilentZNS-policy vs traditional-mapping lane pairs over one shared
  union engine (``repro.core.headline.paper_report``; PR 8's gates:
  DLWA reduction at 10% occupancy >= 80%, wear reduction > 0,
  workload execution speedup > 1x, zero jit-cache growth across
  repeated same-shape dispatches -- see ``check_paper_gates``).

Both speedup comparisons assert metric agreement between the paths
before timing anything.  Usage::

    PYTHONPATH=src python tools/bench.py [--quick] [--repeats 3]
        [--out BENCH_zoneengine.json] [--fleet-out BENCH_fleet.json]
        [--paper-out BENCH_paper.json]
        [--skip-engine] [--skip-fleet] [--skip-paper]
"""

from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import platform
import subprocess
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from benchmarks.common import use_compile_cache  # noqa: E402
from repro.core import workloads  # noqa: E402
from repro.fleet import grid_space  # noqa: E402
from repro.fleet.search import fleet_vs_legacy_speedup  # noqa: E402


# bump when the artifact layout changes in a way bench_table must
# know about (2: run provenance stamped in meta; obs_overhead section;
# 3: array section + scaled legacy fleet timing; 4: BENCH_paper.json
# headline artifact; 5: trace section -- compiled app workloads vs the
# legacy per-op replay)
SCHEMA_VERSION = 5


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "-C", str(_ROOT), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            check=True).stdout.strip()
    except Exception:
        return "unknown"


def _meta(**extra) -> dict:
    import jax

    return {
        "schema_version": SCHEMA_VERSION,
        "device": "zn540/superblock",
        "python": platform.python_version(),
        "machine": platform.machine(),
        "platform": platform.platform(),
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        **extra,
    }


def _sanitize_audit(policies=("traditional", "silent")) -> dict:
    """End-state invariant audit accompanying an artifact: drive a
    canonical fill/finish/reset cycle per alloc policy on the bench
    geometry and run every final device state through the
    :mod:`repro.check` sanitizer.  Raises ``SanitizerError`` on any
    violation; returns the summary stamped into the artifact."""
    from repro.check import assert_states
    from repro.core import engine as zengine
    from repro.core.elements import SUPERBLOCK
    from repro.core.engine import ZoneEngine
    from repro.core.geometry import FlashGeometry, ZoneGeometry

    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=32, pages_per_block=4,
                          page_bytes=4096)
    eng = ZoneEngine(flash, ZoneGeometry(parallelism=4, n_segments=2),
                     SUPERBLOCK, max_active=8)
    zp = eng.cfg.zone_pages
    ops = []
    for z in range(3):
        ops += [(zengine.OP_WRITE, z, zp // 2, zengine.F_HOST),
                (zengine.OP_FINISH, z, 0, 0)]
    ops += [(zengine.OP_RESET, 0, 0, 0),
            (zengine.OP_WRITE, 0, zp, zengine.F_HOST)]
    program = np.asarray(ops, dtype=np.int32)
    dyns = [eng.dyn(alloc_policy=p) for p in policies]
    programs = np.broadcast_to(program, (len(dyns),) + program.shape)
    states, trace = eng.run_batch(eng.init_state(), np.ascontiguousarray(
        programs), zengine.stack_dyn(dyns))
    assert bool(np.asarray(trace.ok).all()), "audit program illegal?"
    assert_states(eng.cfg, states, zengine.stack_dyn(dyns),
                  where="bench sanitize audit")
    return {"checked": True, "lanes": float(len(dyns)),
            "policies": list(policies)}


def bench_engine(args) -> int:
    occs = (np.linspace(0.1, 0.9, 5) if args.quick
            else np.linspace(0.05, 0.95, 16))
    concs = (1, 4) if args.quick else (1, 2, 4, 7)
    rep = workloads.engine_vs_legacy_speedup(
        occupancies=tuple(float(o) for o in occs),
        n_zones=4 if args.quick else 8,
        concurrencies=concs,
        repeats=args.repeats)

    artifact = {
        "dlwa": {
            "ops": rep["dlwa_ops"],
            "legacy_s": rep["dlwa_legacy_s"],
            "engine_s": rep["dlwa_engine_s"],
            "legacy_ops_s": rep["dlwa_legacy_ops_s"],
            "engine_ops_s": rep["dlwa_engine_ops_s"],
            "speedup": rep["dlwa_speedup"],
        },
        "interference": {
            "ops": rep["interference_ops"],
            "legacy_s": rep["interference_legacy_s"],
            "engine_s": rep["interference_engine_s"],
            "legacy_ops_s": rep["interference_legacy_ops_s"],
            "engine_ops_s": rep["interference_engine_ops_s"],
            "speedup": rep["interference_speedup"],
            # PR 6 diagnosis of the 0.96x regression: each concurrency
            # point used to be its own scan shape, so the sweep paid
            # one XLA compile per point per process.  It now NOP-pads
            # to one rectangular batch -> ONE dispatch, and the jit
            # cache must not grow across timed repeats.
            "dispatches": rep["interference_dispatches"],
            "recompiles": rep["interference_recompiles"],
        },
        "meta": _meta(occupancies=len(occs), concurrencies=list(concs),
                      repeats=args.repeats),
    }
    if args.sanitize:
        artifact["sanitize"] = _sanitize_audit()
    args.out.write_text(json.dumps(artifact, indent=2) + "\n")
    for name in ("dlwa", "interference"):
        row = artifact[name]
        print(f"{name}: legacy {row['legacy_ops_s']:.0f} ops/s, "
              f"engine {row['engine_ops_s']:.0f} ops/s, "
              f"speedup {row['speedup']:.1f}x")
    intf = artifact["interference"]
    print(f"interference: {intf['dispatches']:.0f} dispatch(es), "
          f"{intf['recompiles']:.0f} recompile(s) across timed repeats")
    print(f"wrote {args.out}")
    rc = 0
    # the acceptance bar from PR 2: scan-compiled dlwa sweep >= 5x
    if artifact["dlwa"]["speedup"] < 5.0:
        print("WARNING: dlwa speedup below the 5x target", file=sys.stderr)
        rc = 1
    # PR 6: with the recompile leak fixed the batched sweep must not
    # lose to the per-op legacy loop, and the timed repeats must not
    # grow the jit cache (a regrowth here is the 0.96x bug returning)
    if intf["speedup"] < 1.0:
        print("WARNING: interference speedup below the 1x floor",
              file=sys.stderr)
        rc = 1
    if intf["recompiles"] != 0:
        print("WARNING: interference sweep recompiled during timed "
              "repeats (shape-unstable dispatch)", file=sys.stderr)
        rc = 1
    return rc


def _obs_overhead(eng, repeats: int, sanitize: bool = False) -> dict:
    """Telemetry-on vs telemetry-off wall time of the same warmed
    batched ``run_fleet`` dispatch (8 configs x 4 devices)."""
    import gc

    import jax

    from repro.fleet import (N_TENANTS, build_fleet_batch, grid_space,
                             run_fleet)
    from repro.obs import ObsConfig

    configs = grid_space(segments=(22, 11), chunks=(1536, 768),
                         parities=(False, True), wear=(True, False))[:8]
    programs, dyn, _ = build_fleet_batch(eng, configs, n_devices=4,
                                         pad_quantum=64)
    obs = ObsConfig(n_buckets=32, n_tenants=N_TENANTS + 1)

    def once(o):
        # FleetResult is decoded to numpy, which already forces the
        # device sync -- block again anyway in case decode gets lazier
        res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                        parity_tenant=N_TENANTS, obs=o)
        jax.block_until_ready(res.completions)
        return res

    warm = (once(None), once(obs))  # warm both jit variants
    if sanitize:
        from repro.check import assert_states
        for res in warm:
            assert_states(eng.cfg, res.states, dyn,
                          where="obs-overhead warm states")
    # paired back-to-back measurements with GC parked, summarized as
    # the median of per-pair ratios: the dispatch is ~0.2s, where one
    # scheduler hiccup or GC pause swings a min-of-N ratio past the
    # 1.10 gate even though the true overhead is a few percent
    offs, ons = [], []
    gc.collect()
    gc.disable()
    try:
        for _ in range(3 * max(repeats, 3)):
            offs.append(_timed(once, None))
            ons.append(_timed(once, obs))
    finally:
        gc.enable()
    ratios = sorted(b / a for a, b in zip(offs, ons))
    off_s = float(np.median(offs))
    on_s = float(np.median(ons))
    return {
        "n_lanes": float(programs.shape[0]),
        "n_ops": float(programs.shape[1]),
        "off_s": off_s,
        "on_s": on_s,
        "overhead": float(ratios[len(ratios) // 2]),
    }


def _timed(fn, *fn_args) -> float:
    t0 = time.perf_counter()
    fn(*fn_args)
    return time.perf_counter() - t0


def _evaluator_recompiles(eng, generations: int = 4,
                          sanitize: bool = False) -> dict:
    """Jit-cache growth across repeated same-shape Evaluator
    generations -- flat after generation 1 means the dispatch surface
    is shape-stable (pad_quantum doing its job)."""
    from repro.fleet import Evaluator, grid_space
    from repro.obs import Profiler

    configs = grid_space(segments=(22, 11), chunks=(1536,),
                         parities=(False, True), wear=(True,))[:4]
    ev = Evaluator(eng, n_devices=2, profiler=Profiler(),
                   sanitize=sanitize)
    per_gen = []
    for _ in range(generations):
        ev.evaluate(configs)
        per_gen.append(ev.jit_cache()["run_programs"])
    return {
        "generations": float(generations),
        "run_programs_cache_per_gen": [float(c) for c in per_gen],
        "stable_after_warmup": bool(
            len(set(per_gen[1:])) <= 1 and per_gen[1] == per_gen[-1]),
    }


def _bench_array(args) -> dict:
    """The engine-native array comparator + the rebuild-storm
    recompile-stability probe (one shared engine, two identical
    same-shape storm dispatches; the second must not grow the jit
    cache)."""
    from repro.array import (StormScenario, array_vs_legacy_speedup,
                             rebuild_storm)
    from repro.core import engine as zengine
    from repro.core import timing as ctiming
    from repro.core.elements import SUPERBLOCK
    from repro.core.engine import ZoneEngine
    from repro.core.geometry import zn540
    from repro.obs import ObsConfig
    from repro.obs.profile import RecompileCounter

    rep = array_vs_legacy_speedup(
        n_arrays=4 if args.quick else 8,
        n_zones=4 if args.quick else 8,
        repeats=args.repeats,
        legacy_arrays=2)

    flash, zone = zn540()
    eng = ZoneEngine(flash, zone, SUPERBLOCK, max_active=14)
    scenarios = [
        StormScenario(n_devices=3, n_zones_filled=2, occupancy=0.5),
        StormScenario(n_devices=4, n_zones_filled=2, occupancy=0.6),
    ]
    counter = RecompileCounter(run_programs=zengine.run_programs,
                               simulate_fleet_ops=ctiming.simulate_fleet_ops)
    obs = ObsConfig(n_buckets=16, n_tenants=3)
    rebuild_storm(eng, scenarios, obs=obs)          # warm/compile
    before = counter.counts()
    t0 = time.perf_counter()
    storm = rebuild_storm(eng, scenarios, obs=obs)  # must hit the cache
    storm_s = time.perf_counter() - t0
    delta = counter.delta(before)
    rep["storm"] = {
        "n_scenarios": float(len(scenarios)),
        "dispatch_s": storm_s,
        "recompiles": float(sum(delta.values())),
        "scenarios": storm["scenarios"],
    }
    return rep


def _trace_recorders(eng, quick: bool):
    """Record the three application workloads (seed-varied instances)
    into op programs; each recorder is one independent device lane."""
    import repro.storage as S
    from repro.storage.compile import _lsm_jobs

    n_inst = 1 if quick else 2
    recs, labels = [], []
    for inst in range(n_inst):
        for name in ("lsm", "ckpt", "cache"):
            rec = S.RecordingBackend(
                eng.flash, zone_pages=eng.cfg.zone_pages,
                n_zones=eng.cfg.n_zones, max_active=eng.cfg.max_active)
            if name == "lsm":
                cfg = S.scaled_kv_config(
                    rec.zone_pages, eng.flash.page_bytes, seed=inst,
                    n_flushes=6 if quick else 10,
                    max_jobs=_lsm_jobs(rec))
                S.LSMSimulator(S.ZoneFS(rec), cfg).run()
            elif name == "ckpt":
                S.record_checkpoints(rec, S.CheckpointSchedule(
                    n_steps=10 if quick else 24, shards=3, seed=inst))
            else:
                S.record_cache(rec, n_accesses=600 if quick else 2000,
                               n_keys=64, seed=inst,
                               capacity_zones=min(6, rec.n_zones),
                               obj_pages=4)
            recs.append(rec)
            labels.append(f"{name}{inst}")
    return recs, labels


def _legacy_replay_trace(eng, rec) -> float:
    """Replay one recorder's rows through the per-op legacy device;
    return its final DLWA (the exactness oracle)."""
    from repro.core import engine as zengine
    from repro.core.device_legacy import LegacyZNSDevice

    leg = LegacyZNSDevice(eng.flash, eng.zone_geom, eng.spec,
                          max_active=eng.cfg.max_active)
    for op, zone, n, flags, _tenant in rec.program().tolist():
        if op == zengine.OP_WRITE:
            leg.zone_write(zone, n, host=bool(flags & zengine.F_HOST))
        elif op == zengine.OP_FINISH:
            leg.zone_finish(zone)
        elif op == zengine.OP_RESET:
            leg.zone_reset(zone)
        elif op == zengine.OP_READ:
            leg.zone_read(zone, np.arange(n))
    return leg.dlwa


def _bench_trace(args) -> dict:
    """PR 9's comparator: ZoneFS/LSM, checkpoint-burst, and flash-cache
    traffic compiled to op programs and replayed through ONE batched
    dispatch vs the same op streams through the per-op legacy device,
    plus a zero-recompile probe across repeated same-shape dispatches."""
    import repro.storage as S
    from repro.core import engine as zengine
    from repro.core import timing as ctiming
    from repro.core.elements import SUPERBLOCK
    from repro.core.engine import ZoneEngine
    from repro.core.geometry import FlashGeometry, ZoneGeometry
    from repro.obs.profile import RecompileCounter

    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=32, pages_per_block=4,
                          page_bytes=4096)
    eng = ZoneEngine(flash, ZoneGeometry(parallelism=4, n_segments=2),
                     SUPERBLOCK, max_active=8)
    recs, labels = _trace_recorders(eng, bool(args.quick))
    n_ops = float(sum(len(r) for r in recs))

    counter = RecompileCounter(run_programs=zengine.run_programs,
                               simulate_fleet_ops=ctiming.simulate_fleet_ops)
    res = S.replay_recorders(eng, recs, n_tenants=1,   # warm/compile
                             sanitize=bool(args.sanitize))
    # exactness before timing: every compiled lane's DLWA must equal
    # the legacy per-op replay of the identical op stream
    t0 = time.perf_counter()
    legacy_dlwa = [_legacy_replay_trace(eng, rec) for rec in recs]
    legacy_s = time.perf_counter() - t0
    for lane, (rec, want) in enumerate(zip(recs, legacy_dlwa)):
        got = S.lane_metrics(eng, res, lane)["dlwa"]
        assert abs(got - want) < 1e-12, \
            f"lane {labels[lane]}: engine dlwa {got} != legacy {want}"

    before = counter.counts()
    engine_s = min(_timed(S.replay_recorders, eng, recs)
                   for _ in range(args.repeats))
    recompiles = float(sum(counter.delta(before).values()))
    return {
        "n_lanes": float(len(recs)),
        "workloads": labels,
        "recorded_ops": n_ops,
        "legacy_s": legacy_s,
        "engine_s": engine_s,
        "speedup": legacy_s / engine_s if engine_s else float("inf"),
        "recompiles": recompiles,
        "lane_dlwa": [float(d) for d in legacy_dlwa],
    }


def bench_fleet(args) -> int:
    from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
    from repro.core.engine import ZoneEngine
    from repro.core.geometry import zn540
    from repro.fleet import SearchSpace, evolve_vs_random

    configs = None
    space = SearchSpace()
    if args.quick:
        configs = grid_space(segments=(22, 11), chunks=(1536,),
                             parities=(False, True), wear=(True,))
        space = SearchSpace(chunks=(1536,), parities=(False, True))
    # the legacy legs are timed on an 8-config prefix and scaled (the
    # per-op pipeline is per-config sequential; the DLWA exactness
    # assert inside still covers every config)
    rep = fleet_vs_legacy_speedup(configs=configs, repeats=args.repeats,
                                  legacy_configs=8)

    # mixed element specs in ONE union-config dispatch vs the per-spec
    # legacy pipeline (members built with each config's actual spec;
    # DLWA agreement asserted inside before timing)
    mixed_specs = (SUPERBLOCK, BLOCK, vchunk(2))
    mixed_configs = grid_space(
        segments=(22,) if args.quick else (22, 11),
        chunks=(1536,), parities=(False,), wear=(True,),
        specs=mixed_specs)
    mixed = fleet_vs_legacy_speedup(configs=mixed_configs,
                                    specs=mixed_specs,
                                    repeats=args.repeats)
    mixed["n_specs"] = float(len(mixed_specs))

    # adaptive search: dispatched budget to reach the random-32 target
    flash, zone = zn540()
    eng = ZoneEngine(flash, zone, SUPERBLOCK, max_active=14)
    evo = evolve_vs_random(eng, space=space, random_n=32, seed=0,
                           n_devices=4)

    # PR 6 flight recorder: telemetry carried through the scan must
    # stay within 10% of the bare dispatch, and repeated same-shape
    # Evaluator generations must not grow the jit cache
    overhead = _obs_overhead(eng, repeats=args.repeats,
                             sanitize=bool(args.sanitize))
    recomp = _evaluator_recompiles(eng, sanitize=bool(args.sanitize))

    # PR 7: engine-native ZNS-RAID vs the object ZNSArray replay, plus
    # the rebuild-storm recompile-stability probe
    arr = _bench_array(args)

    # PR 9: application traces (LSM/checkpoint/flash-cache) compiled to
    # op programs and batch-replayed vs the per-op legacy device
    trace = _bench_trace(args)

    artifact = {
        "fleet_sweep": rep,
        "mixed_spec": mixed,
        "evolve": evo,
        "obs_overhead": overhead,
        "evaluator_recompiles": recomp,
        "array": arr,
        "trace": trace,
        "meta": _meta(repeats=args.repeats, quick=bool(args.quick),
                      legacy_timed_configs=rep["legacy_timed_configs"],
                      legacy_scale=rep["legacy_scale"],
                      array_legacy_timed=arr["legacy_timed_arrays"],
                      array_legacy_scale=arr["legacy_scale"]),
    }
    if args.sanitize:
        artifact["sanitize"] = _sanitize_audit()
    args.fleet_out.write_text(json.dumps(artifact, indent=2) + "\n")
    print(f"fleet: {rep['n_configs']:.0f} configs x "
          f"{rep['n_devices']:.0f} devices, "
          f"legacy {rep['legacy_s']:.2f}s vs engine {rep['engine_s']:.2f}s "
          f"-> speedup {rep['speedup']:.1f}x "
          f"(replay-only {rep['replay_speedup']:.1f}x)")
    print(f"mixed-spec: {mixed['n_configs']:.0f} configs over "
          f"{len(mixed_specs)} element specs in one dispatch, "
          f"legacy {mixed['legacy_s']:.2f}s vs engine "
          f"{mixed['engine_s']:.2f}s -> speedup {mixed['speedup']:.1f}x")
    print(f"evolve: target {evo['random']['best_objective']:.4f} "
          f"({'reached' if evo['evolve']['reached_target'] else 'MISSED'}) "
          f"with {evo['evolve']['n_evals']:.1f} evals / "
          f"{evo['evolve']['n_dispatches']:.0f} dispatches vs random's "
          f"{evo['random']['n_evals']:.0f} / "
          f"{evo['random']['n_dispatches']:.0f} "
          f"-> {evo['n_evals_savings']:.1f}x eval savings")
    print(f"obs: telemetry-on {overhead['on_s']:.3f}s vs off "
          f"{overhead['off_s']:.3f}s -> {overhead['overhead']:.3f}x "
          f"overhead; evaluator run_programs cache per generation "
          f"{recomp['run_programs_cache_per_gen']}")
    print(f"array: {arr['n_arrays']:.0f} arrays ({arr['lane_ops']:.0f} "
          f"lane-ops), legacy {arr['legacy_s']:.2f}s "
          f"({arr['legacy_timed_arrays']:.0f} timed, "
          f"x{arr['legacy_scale']:.1f} scaled) vs engine "
          f"{arr['engine_s']:.2f}s -> speedup {arr['speedup']:.1f}x; "
          f"storm {arr['storm']['n_scenarios']:.0f} scenarios in "
          f"{arr['storm']['dispatch_s']:.2f}s, "
          f"{arr['storm']['recompiles']:.0f} recompile(s)")
    print(f"trace: {trace['n_lanes']:.0f} workload lanes "
          f"({trace['recorded_ops']:.0f} recorded ops), legacy "
          f"{trace['legacy_s']:.2f}s vs engine {trace['engine_s']:.2f}s "
          f"-> speedup {trace['speedup']:.1f}x, "
          f"{trace['recompiles']:.0f} recompile(s)")
    print(f"wrote {args.fleet_out}")
    rc = 0
    # PR 3's acceptance bar: batched fleet sweep >= 5x
    if rep["speedup"] < 5.0:
        print("WARNING: fleet speedup below the 5x target", file=sys.stderr)
        rc = 1
    # PR 4's acceptance bar: random-best matched on <= half the evals
    if (not evo["evolve"]["reached_target"]
            or evo["n_evals_savings"] < 2.0):
        print("WARNING: evolve missed the <=half-budget-to-random-best "
              "target", file=sys.stderr)
        rc = 1
    # PR 6's acceptance bars: telemetry within 10%, flat jit cache
    if overhead["overhead"] > 1.10:
        print("WARNING: telemetry overhead above the 1.10x budget",
              file=sys.stderr)
        rc = 1
    if not recomp["stable_after_warmup"]:
        print("WARNING: Evaluator jit cache grew across same-shape "
              "generations (recompile leak)", file=sys.stderr)
        rc = 1
    # PR 7's acceptance bars: engine-native array >= 5x over the object
    # replay, rebuild-storm dispatch shape-stable
    if arr["speedup"] < 5.0:
        print("WARNING: array speedup below the 5x target", file=sys.stderr)
        rc = 1
    if arr["storm"]["recompiles"] != 0:
        print("WARNING: rebuild-storm dispatch recompiled on a repeated "
              "same-shape call", file=sys.stderr)
        rc = 1
    # PR 9's acceptance bars: compiled app traces >= 5x over the per-op
    # legacy replay, dispatch shape-stable across repeats
    if trace["speedup"] < 5.0:
        print("WARNING: trace-compile speedup below the 5x target",
              file=sys.stderr)
        rc = 1
    if trace["recompiles"] != 0:
        print("WARNING: trace replay recompiled on a repeated same-shape "
              "dispatch", file=sys.stderr)
        rc = 1
    return rc


# the paper's summary claims, as floors the artifact must clear
PAPER_DLWA_REDUCTION_FLOOR = 0.80   # paper: 92% at 10% occupancy
PAPER_WEAR_REDUCTION_FLOOR = 0.0    # paper: up to 12% less wear
PAPER_EXEC_SPEEDUP_FLOOR = 1.0      # paper: up to 3.7x faster


def check_paper_gates(artifact: dict) -> int:
    """PR 8's acceptance bars over a ``BENCH_paper.json`` artifact.

    Pure function of the artifact dict (no benchmarking) so the gate
    logic is unit-testable: returns 0 when every gate passes, 1
    otherwise, printing one stderr WARNING per failed gate."""
    rc = 0
    dlwa = artifact["dlwa"]["reduction_at_10pct"]
    if dlwa < PAPER_DLWA_REDUCTION_FLOOR:
        print(f"WARNING: DLWA reduction at 10% occupancy {dlwa:.1%} "
              f"below the {PAPER_DLWA_REDUCTION_FLOOR:.0%} floor",
              file=sys.stderr)
        rc = 1
    wear = artifact["wear"]["wear_reduction"]
    if wear <= PAPER_WEAR_REDUCTION_FLOOR:
        print(f"WARNING: silent policy saved no wear "
              f"(wear reduction {wear:.1%})", file=sys.stderr)
        rc = 1
    speedup = artifact["exec"]["speedup"]
    if speedup <= PAPER_EXEC_SPEEDUP_FLOOR:
        print(f"WARNING: workload execution speedup {speedup:.2f}x "
              f"not above the 1x floor", file=sys.stderr)
        rc = 1
    if artifact["recompiles"]["delta_total"] != 0:
        print("WARNING: paper figures recompiled on a repeated "
              "same-shape dispatch", file=sys.stderr)
        rc = 1
    return rc


def bench_paper(args) -> int:
    from repro.core import headline

    occs = ((0.1, 0.3, 0.7) if args.quick
            else headline.DEFAULT_OCCUPANCIES)
    report = headline.paper_report(
        occupancies=occs,
        wear_zones=4 if args.quick else 8,
        wear_cycles=4 if args.quick else 8,
        exec_cycles=2 if args.quick else 4)
    report["meta"] = _meta(quick=bool(args.quick),
                           occupancies=len(occs))
    if args.sanitize:
        report["sanitize"] = _sanitize_audit()
    args.paper_out.write_text(json.dumps(report, indent=2) + "\n")

    d, w, x = report["dlwa"], report["wear"], report["exec"]
    print(f"paper/dlwa: reduction at 10% occupancy "
          f"{d['reduction_at_10pct']:.1%} "
          f"({d['traditional_dlwa'][0]:.2f} -> {d['silent_dlwa'][0]:.2f};"
          f" paper claims 92%)")
    print(f"paper/wear: {w['traditional_erases']:.0f} -> "
          f"{w['silent_erases']:.0f} block erases "
          f"(-{w['wear_reduction']:.1%})")
    print(f"paper/exec: {x['traditional_s']:.2f}s -> {x['silent_s']:.2f}s "
          f"({x['speedup']:.2f}x); recompiles on repeat "
          f"{report['recompiles']['delta_total']:.0f}")
    print(f"wrote {args.paper_out}")
    return check_paper_gates(report)


def main() -> int:
    # allow_abbrev off: a mistyped/abbreviated flag (e.g. `--skip`)
    # must exit non-zero instead of silently running everything under
    # argparse's prefix guessing
    ap = argparse.ArgumentParser(description=__doc__, allow_abbrev=False)
    ap.add_argument("--out", type=pathlib.Path,
                    default=_ROOT / "BENCH_zoneengine.json")
    ap.add_argument("--fleet-out", type=pathlib.Path,
                    default=_ROOT / "BENCH_fleet.json")
    ap.add_argument("--paper-out", type=pathlib.Path,
                    default=_ROOT / "BENCH_paper.json")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI smoke)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run repro.check's DeviceState sanitizer on the "
                         "warm dispatch states and stamp an end-state "
                         "invariant audit into each artifact (timed "
                         "repeats stay un-sanitized)")
    ap.add_argument("--skip-engine", action="store_true")
    ap.add_argument("--skip-fleet", action="store_true")
    ap.add_argument("--skip-paper", action="store_true")
    args = ap.parse_args()
    if args.skip_engine and args.skip_fleet and args.skip_paper:
        ap.error("--skip-engine, --skip-fleet and --skip-paper together "
                 "leave nothing to benchmark")

    use_compile_cache()
    rc = 0
    if not args.skip_engine:
        rc |= bench_engine(args)
    if not args.skip_fleet:
        rc |= bench_fleet(args)
    if not args.skip_paper:
        rc |= bench_paper(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
