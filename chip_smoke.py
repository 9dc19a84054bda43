"""One-chip bring-up smoke of the recorded-workload fleet path.

Drives the simulator's main path once, on the paper's full zn540
geometry (4 LUNs x 1056 blocks of 768 16-KiB pages, 48 zones, 14
active), through the entry points a user calls:

* ``fleet``: the grid that ``benchmarks/fleet_search.py --workload lsm
  --policies traditional,silent`` scores -- the recorded lsm tenant mix
  over the superblock/block/vchunk2 union engine, 96 configs x 4 member
  devices = 384 lanes in one ``run_programs`` and one
  ``simulate_fleet_ops`` dispatch.  The first call goes through
  ``repro.fleet.run_fleet`` and its result is checked; the warm call
  goes through ``Evaluator.evaluate``, whose rows must equal the
  checked result's.
* ``lsm`` / ``ckpt`` / ``cache``: ``repro.storage.run_workload``, once
  per ``alloc_policy`` (the traditional call compiles, the silent call
  is the warm one); lanes rotate through the union's element specs.

Every result is checked against references that do not run the code
under test:

* every lane's ``trace.ok`` equals the numpy verifier
  (``repro.check.verify_programs``);
* on the checked lanes (both policies, all three specs) DLWA and dummy
  pages equal a per-op ``LegacyZNSDevice`` replay of the lane's rows,
  run on the host CPU; so do block erases and per-element wear on
  traditional lanes (the legacy oracle has no silent allocator);
* every lane's final state passes the ``repro.check`` sanitizer;
* every lane's completions, latencies and makespan equal the numpy
  float32 busy clock :func:`numpy_clock`, bit for bit.

Each phase prints one JSON line: lanes, ops, first-call and compile
seconds, warm seconds, peak device bytes and the recompiles over the
warm call (which must be 0).  These are bring-up figures, not a
benchmark.  The last line is ``{"ok": true, "device": {...}}``; when JAX
finds no TPU, or any check fails, the script exits non-zero without
printing it.  Everything runs in this one process::

    python chip_smoke.py
"""

from __future__ import annotations

import itertools
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.storage as storage  # noqa: E402  (registers the workload mixes)
from benchmarks.common import use_compile_cache  # noqa: E402
from repro.check import check_states, verify_programs  # noqa: E402
from repro.core import engine as zengine  # noqa: E402
from repro.core.device import ZoneState  # noqa: E402
from repro.core.device_legacy import LegacyZNSDevice  # noqa: E402
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk  # noqa: E402
from repro.core.engine import ZoneEngine  # noqa: E402
from repro.core.geometry import ZoneGeometry, zn540  # noqa: E402
from repro.fleet import (N_TENANTS, Evaluator, build_fleet_batch,  # noqa: E402
                         config_report, grid_space, run_fleet)
from repro.obs import Profiler, RecompileCounter  # noqa: E402

SPECS = (SUPERBLOCK, BLOCK, vchunk(2))
POLICIES = ("traditional", "silent")
N_DEVICES = 4
WORKLOADS = ("lsm", "ckpt", "cache")


class CheckFailed(Exception):
    """A result of the code under test disagreed with its reference."""


# --------------------------------------------------------------------- #
# references
# --------------------------------------------------------------------- #
def numpy_clock(cols, pages, tenants, t_page, n_luns: int, n_tenants: int):
    """The closed-loop busy clock of ``simulate_fleet_ops`` in numpy
    float32, one op after another (lanes side by side): an op holds all
    of its zone's LUN columns for ``ceil(pages / P) * t_page`` and
    starts once they are free and its tenant's previous op is done."""
    n_lanes, n_ops, p = cols.shape
    rows = np.arange(n_lanes)
    lun_free = np.zeros((n_lanes, n_luns), np.float32)
    ten_done = np.zeros((n_lanes, n_tenants), np.float32)
    done = np.zeros((n_lanes, n_ops), np.float32)
    lat = np.zeros((n_lanes, n_ops), np.float32)
    for i in range(n_ops):
        act = pages[:, i] > 0
        c, t = cols[:, i], tenants[:, i]
        dur = ((pages[:, i] + p - 1) // p).astype(np.float32) * t_page[:, i]
        prev = ten_done[rows, t]
        d = np.maximum(lun_free[rows[:, None], c].max(axis=1), prev) + dur
        done[act, i] = d[act]
        lat[act, i] = (d - prev)[act]
        lun_free[rows[act, None], c[act]] = d[act, None]
        ten_done[rows[act], t[act]] = d[act]
    return done, lat, lun_free.max(axis=1)


def legacy_replay(flash, zone_geom: ZoneGeometry, spec, wear_aware: bool,
                  max_active: int, program: np.ndarray) -> LegacyZNSDevice:
    """Replay one lane's rows through the per-op legacy device."""
    leg = LegacyZNSDevice(flash, zone_geom, spec, max_active=max_active,
                          wear_aware=wear_aware)
    for op, zone, n, flags in program[:, :4].tolist():
        if op == zengine.OP_ALLOC:
            # an ALLOC row maps an EMPTY zone where it stands (a striped
            # lane allocates before its first chunk arrives), else no-op
            if leg.zones[zone].state is ZoneState.EMPTY:
                leg._allocate_zone(zone)
        elif op == zengine.OP_WRITE:
            leg.zone_write(zone, n, host=bool(flags & zengine.F_HOST))
        elif op == zengine.OP_FINISH:
            leg.zone_finish(zone)
        elif op == zengine.OP_RESET:
            leg.zone_reset(zone)
        elif op == zengine.OP_READ:
            leg.zone_read(zone, np.arange(n))
    return leg


# --------------------------------------------------------------------- #
# checks (each raises CheckFailed naming the first difference)
# --------------------------------------------------------------------- #
def check_ok(res) -> None:
    """``trace.ok`` of every lane == the numpy verifier's prediction."""
    want = np.stack([r.ok for r in verify_programs(res.cfg, res.programs,
                                                   res.dyn)])
    bad = np.argwhere(res.ok != want)
    if bad.size:
        lane, i = bad[0]
        raise CheckFailed(
            f"trace.ok differs from the verifier first at lane {lane} op "
            f"{i} (row {res.programs[lane, i].tolist()}): device "
            f"{bool(res.ok[lane, i])}, verifier {bool(want[lane, i])}")


def check_clock(res, flash) -> None:
    """Completions, latencies and makespans == :func:`numpy_clock`, bit
    for bit, on every lane."""
    op = res.programs[:, :, 0]
    t_page = np.where(op == zengine.OP_READ,
                      np.float32(flash.t_read + flash.t_xfer),
                      np.float32(flash.t_prog + flash.t_xfer))
    done, lat, span = numpy_clock(res.cols, res.pages, res.tenants, t_page,
                                  flash.n_luns, res.parity_tenant + 1)
    for name, got, want in (("completion", res.completions, done),
                            ("latency", res.latencies, lat)):
        bad = np.argwhere(np.asarray(got, np.float32).view(np.int32)
                          != want.view(np.int32))
        if bad.size:
            lane, i = bad[0]
            raise CheckFailed(
                f"{name} differs from the numpy clock first at lane "
                f"{lane} op {i}: device {got[lane, i]!r}, numpy "
                f"{want[lane, i]!r}")
    bad = np.flatnonzero(np.asarray(res.makespans, np.float32).view(np.int32)
                         != span.view(np.int32))
    if bad.size:
        lane = bad[0]
        raise CheckFailed(
            f"makespan differs from the numpy clock first at lane {lane}: "
            f"device {res.makespans[lane]!r}, numpy {span[lane]!r}")


def check_states_sane(res) -> None:
    """Every lane's final state passes the ``repro.check`` sanitizer
    (zone/element maps agree, erases reconcile with element wear)."""
    host = jax.tree_util.tree_map(np.asarray, res.states)
    for lane, violations in enumerate(check_states(res.cfg, host, res.dyn)):
        if violations:
            raise CheckFailed(f"lane {lane} state: {violations[0]}")


def check_lanes(eng: ZoneEngine, res, lanes) -> int:
    """Each ``(lane, spec, n_segments, wear_aware, policy)`` against a
    legacy replay of its rows on the host CPU; returns lanes checked.
    Erases and wear are where the silent policy acts, and the legacy
    oracle has no silent allocator: silent lanes are held to the
    policy-invariant host pages, dummy pages and DLWA."""
    with jax.default_device(jax.devices("cpu")[0]):
        for lane, spec, n_segments, wear_aware, policy in lanes:
            geom = ZoneGeometry(eng.zone_geom.parallelism, n_segments)
            leg = legacy_replay(eng.flash, geom, spec, wear_aware,
                                eng.cfg.max_active, res.programs[lane])
            got = storage.lane_metrics(eng, res, lane)
            want = {"host_pages": float(leg.host_pages),
                    "dummy_pages": float(leg.dummy_pages),
                    "dlwa": leg.dlwa}
            if policy == "traditional":
                want["block_erases"] = float(leg.block_erases)
            ctx = f"lane {lane} ({spec.name}, {policy}, S{n_segments})"
            for key, w in want.items():
                if got[key] != w:
                    raise CheckFailed(f"{ctx}: {key} {got[key]!r}, legacy "
                                      f"replay {w!r}")
            if policy == "traditional":
                wear = np.asarray(res.states.elem_wear[lane])[
                    eng.member_element_ids(spec)]
                if not np.array_equal(wear, leg.elem_wear):
                    e = int(np.flatnonzero(wear != leg.elem_wear)[0])
                    raise CheckFailed(
                        f"{ctx}: element {e} wear {int(wear[e])}, legacy "
                        f"replay {int(leg.elem_wear[e])}")
    return len(lanes)


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _compile_s(prof: Profiler) -> float:
    return sum(s["trace_s"] + s["lower_s"] + s["compile_s"]
               for s in prof.sections.values())


def _shape(res) -> dict:
    lanes, ops = res.programs.shape[:2]
    return {"lanes": lanes, "ops": ops, "lane_ops": lanes * ops,
            "real_ops": int((res.programs[:, :, 0] != zengine.OP_NOP).sum())}


def _recompiles(phase: str, delta: dict) -> int:
    """Compiles over a warm call; any is a failure."""
    if sum(delta.values()):
        raise CheckFailed(f"{phase}: the warm call recompiled {delta}")
    return 0


def fleet_phase(eng: ZoneEngine, device, *, segments=(22, 11),
                chunks=(1536, 3072)) -> dict:
    """The ``fleet_search.py --workload lsm`` grid, both policies."""
    configs = grid_space(mixes=("lsm",), segments=segments, chunks=chunks,
                         specs=SPECS, policies=POLICIES)
    ev = Evaluator(eng, n_devices=N_DEVICES, profiler=Profiler())
    programs, dyn, _ = build_fleet_batch(eng, configs, n_devices=N_DEVICES,
                                         pad_quantum=ev.pad_quantum)
    prof = Profiler()
    t0 = time.perf_counter()
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                    profiler=prof)
    first_s = time.perf_counter() - t0

    counter = RecompileCounter.engine_default()
    before = counter.counts()
    t0 = time.perf_counter()
    rows = ev.evaluate(configs)       # host floats: the dispatch is done
    warm_s = time.perf_counter() - t0
    recompiles = _recompiles("fleet", counter.delta(before))

    check_ok(res)
    check_clock(res, eng.flash)
    check_states_sane(res)
    for k, row in enumerate(rows):
        lanes = np.arange(k * N_DEVICES, (k + 1) * N_DEVICES)
        for key, want in config_report(res, eng, lanes).items():
            if row[key] != want:
                raise CheckFailed(f"Evaluator row {row['config']}: {key} "
                                  f"{row[key]!r}, checked dispatch {want!r}")
    # two configs per (policy, spec), spread over the other axes and
    # the member devices
    picked = []
    for j, (p, s) in enumerate(itertools.product(POLICIES, SPECS)):
        match = [k for k, fc in enumerate(configs)
                 if fc.alloc_policy == p and fc.spec == s]
        for k, d in ((match[j % len(match)], j % N_DEVICES),
                     (match[-1 - j % len(match)], (j + 2) % N_DEVICES)):
            picked.append((k * N_DEVICES + d, s, configs[k].n_segments,
                           configs[k].wear_aware, p))
    checked = check_lanes(eng, res, picked)
    return {"phase": "fleet", "configs": len(configs), **_shape(res),
            "first_call_s": first_s, "compile_s": _compile_s(prof),
            "warm_s": warm_s,
            "warm_sections_s": {n: s["wall_s"] for n, s
                                in ev.profiler.sections.items()},
            "peak_bytes_in_use": _peak_bytes(device),
            "recompiles_warm": recompiles,
            "checks": {"ok_lanes": len(configs) * N_DEVICES,
                       "clock_lanes": len(configs) * N_DEVICES,
                       "legacy_lanes": checked,
                       "evaluator_rows": len(rows)}}


def workload_phase(eng: ZoneEngine, device, name: str, rotate: int = 0
                   ) -> dict:
    """``run_workload(name)`` under each policy (lanes rotate specs)."""
    n_lanes = 2
    specs = [[SPECS[(n_lanes * c + lane + rotate) % len(SPECS)]
              for lane in range(n_lanes)] for c in range(len(POLICIES))]

    def call(c, profiler=None):
        dyns = [eng.dyn(spec=s, alloc_policy=POLICIES[c]) for s in specs[c]]
        res, _ = storage.run_workload(eng, name, n_lanes=n_lanes, dyns=dyns,
                                      profiler=profiler)
        jax.block_until_ready(res.states)
        return res

    prof = Profiler()
    t0 = time.perf_counter()
    first = call(0, prof)
    first_s = time.perf_counter() - t0
    counter = RecompileCounter.engine_default()
    before = counter.counts()
    t0 = time.perf_counter()
    warm = call(1)
    warm_s = time.perf_counter() - t0
    recompiles = _recompiles(name, counter.delta(before))

    checked = 0
    for c, res in enumerate((first, warm)):
        check_ok(res)
        check_clock(res, eng.flash)
        check_states_sane(res)
        checked += check_lanes(
            eng, res, [(lane, s, eng.zone_geom.n_segments,
                        eng.cfg.wear_aware, POLICIES[c])
                       for lane, s in enumerate(specs[c])])
    return {"phase": name, **_shape(first), "first_call_s": first_s,
            "compile_s": _compile_s(prof), "warm_s": warm_s,
            "peak_bytes_in_use": _peak_bytes(device),
            "recompiles_warm": recompiles,
            "checks": {"ok_lanes": 2 * n_lanes, "clock_lanes": 2 * n_lanes,
                       "legacy_lanes": checked}}


def main() -> int:
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device.platform!r}); nothing was run", file=sys.stderr)
        return 1
    # full zn540 widths and the entry points' own lane counts: nothing cut
    print(json.dumps({"geometry": "zn540", "cuts": [],
                      "compile_cache": use_compile_cache()}), flush=True)
    flash, zone = zn540()
    eng = ZoneEngine(flash, zone, SPECS, max_active=14)

    def emit(line: dict) -> None:
        print(json.dumps(line), flush=True)
        if line["peak_bytes_in_use"] is None:
            raise CheckFailed(f"{line['phase']}: the device reports no "
                              f"peak_bytes_in_use")

    try:
        emit(fleet_phase(eng, device))
        for w, name in enumerate(WORKLOADS):
            emit(workload_phase(eng, device, name, w))
    except CheckFailed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
