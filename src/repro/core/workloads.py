"""The paper's benchmark workloads (§6.1) on the emulated device.

* ``dlwa_benchmark``        -- fill zones to a target occupancy, FINISH,
                               count dummy pages (Fig. 4a / 7a / 8).
* ``interference_benchmark``-- N zones being FINISHed while the host
                               writes N other zones (Fig. 4b / 7d, Table 3).
* ``write_benchmark``       -- FIO-like sequential writes, varying request
                               size and concurrent zones (Fig. 9).
* ``alloc_latency_benchmark``-- median zone-allocation latency (Table 4).

Each benchmark also has a **batched engine driver** (``*_engine`` /
``dlwa_sweep_engine``) that encodes the workload as an op program and
executes it through :mod:`repro.core.engine` -- a whole occupancy sweep
runs as one vmapped ``lax.scan`` instead of per-op Python calls, and the
interference benchmark runs as a single fused finish+host-write program.
The engine drivers are metric-identical to the per-op paths
(differential-tested against ``LegacyZNSDevice`` in
``tests/test_engine_diff.py``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import engine as zengine
from repro.core import timing
from repro.core.device import IOTrace, ZNSDevice
from repro.core.elements import ElementSpec
from repro.core.geometry import FlashGeometry, ZoneGeometry


def make_device(flash: FlashGeometry, zone: ZoneGeometry, spec: ElementSpec,
                *, max_active: int = 14) -> ZNSDevice:
    return ZNSDevice(flash, zone, spec, max_active=max_active)


# --------------------------------------------------------------------- #
# DLWA benchmark (paper Fig. 4a, 7a, 8)
# --------------------------------------------------------------------- #
def dlwa_benchmark(dev: ZNSDevice, *, occupancy: float,
                   n_zones: Optional[int] = None) -> Dict[str, float]:
    """Fill ``n_zones`` zones to ``occupancy`` then FINISH each; report
    dummy pages (pages 'finished') and DLWA."""
    n_zones = n_zones or min(8, dev.n_zones)
    pages = max(1, int(round(dev.zone_pages * occupancy)))
    pages = min(pages, dev.zone_pages)
    host0, dummy0 = dev.host_pages, dev.dummy_pages
    for z in range(n_zones):
        dev.zone_write(z, pages)
        dev.zone_finish(z)
    host = dev.host_pages - host0
    dummy = dev.dummy_pages - dummy0
    return {
        "occupancy": occupancy,
        "host_pages": float(host),
        "dummy_pages": float(dummy),
        "dummy_pages_per_zone": dummy / n_zones,
        "dlwa": (host + dummy) / host if host else 1.0,
    }


# --------------------------------------------------------------------- #
# Interference benchmark (paper Fig. 4b, 7d, Table 3)
# --------------------------------------------------------------------- #
def interference_benchmark(dev: ZNSDevice, *, concurrency: int,
                           fill_occupancy: float = 0.4,
                           host_pages_per_zone: Optional[int] = None
                           ) -> Dict[str, float]:
    """``concurrency`` zones are FINISHed while the host writes to
    ``concurrency`` other zones.  Interference = host-only throughput /
    host throughput under concurrent FINISH."""
    fill = max(1, int(round(dev.zone_pages * fill_occupancy)))
    hpz = host_pages_per_zone or fill

    # victims: partially filled zones that will be finished
    victims = list(range(concurrency))
    writers = list(range(concurrency, 2 * concurrency))
    for z in victims:
        dev.zone_write(z, fill)

    host_traces: List[IOTrace] = []
    for z in writers:
        tr = dev.zone_write(z, hpz, trace=True)
        host_traces.append(tr)

    finish_traces: List[IOTrace] = []
    for z in victims:
        tr = dev.zone_finish(z, trace=True)
        if tr is not None and len(tr.luns):
            finish_traces.append(tr)

    # baseline: host streams alone
    base = timing.run_trace(dev.flash, host_traces)
    base_tp = sum(base[f"owner{i}_throughput_pages_s"]
                  for i in range(len(host_traces)))
    # contended: host + finish dummy streams interleaved
    cont = timing.run_trace(dev.flash, host_traces + finish_traces)
    cont_tp = sum(cont[f"owner{i}_throughput_pages_s"]
                  for i in range(len(host_traces)))
    factor = base_tp / cont_tp if cont_tp else float("inf")
    return {
        "concurrency": float(concurrency),
        "baseline_pages_s": base_tp,
        "contended_pages_s": cont_tp,
        "interference": factor,
        "dummy_pages": float(sum(len(t.luns) for t in finish_traces)),
    }


# --------------------------------------------------------------------- #
# FIO-like raw write benchmark (paper Fig. 9)
# --------------------------------------------------------------------- #
def write_benchmark(dev: ZNSDevice, *, request_kib: int, n_jobs: int,
                    mib_per_job: int = 16) -> Dict[str, float]:
    """``n_jobs`` concurrent sequential writers, one dedicated zone each,
    fixed request size.  Reports aggregate bandwidth (MiB/s)."""
    pages_per_req = max(1, request_kib * 1024 // dev.flash.page_bytes)
    reqs_per_job = max(1, mib_per_job * 1024 * 1024
                       // (pages_per_req * dev.flash.page_bytes))
    total_pages = pages_per_req * reqs_per_job
    total_pages = min(total_pages, dev.zone_pages)

    traces: List[IOTrace] = []
    for j in range(n_jobs):
        tr = dev.zone_write(j, total_pages, trace=True)
        traces.append(tr)
    stats = timing.run_trace(dev.flash, traces)
    return {
        "request_kib": float(request_kib),
        "n_jobs": float(n_jobs),
        "pages": float(stats["n"]),
        "bandwidth_mib_s": timing.write_bandwidth_mib_s(dev.flash, stats),
        "makespan_s": stats["makespan_s"],
    }


# --------------------------------------------------------------------- #
# Zone-allocation latency (paper Table 4)
# --------------------------------------------------------------------- #
def alloc_latency_benchmark(dev: ZNSDevice, *, n_allocs: int = 32
                            ) -> Dict[str, float]:
    """Median wall-clock latency of zone allocation.  Exercises the
    allocate -> write -> finish -> reset cycle so re-allocation hits the
    deferred-erase path too."""
    n = min(n_allocs, dev.n_zones)
    # Warm up jit *before* timing: every compilable path (engine op
    # switch, or the legacy allocator's primary window + cheapest-groups
    # fallback) -- otherwise first-call compilation lands in the sample
    # set and skews small-sample medians (paper Table 4 methodology).
    warmup = getattr(dev, "warmup_alloc", None)
    if warmup is not None:
        warmup()
    dev.zone_write(0, 1)
    dev.zone_finish(0)
    dev.zone_reset(0)
    dev.alloc_latencies_us.clear()
    for i in range(n):
        z = i % max(1, dev.n_zones // 2)
        dev.zone_write(z, 1)
        dev.zone_finish(z)
        dev.zone_reset(z)
    return {
        "n_allocs": float(len(dev.alloc_latencies_us)),
        "median_us": dev.median_alloc_latency_us(),
        "mean_us": float(np.mean(dev.alloc_latencies_us)),
    }


# --------------------------------------------------------------------- #
# Batched engine drivers: workloads as op programs (one compiled scan)
# --------------------------------------------------------------------- #
def make_engine(flash: FlashGeometry, zone: ZoneGeometry,
                spec: ElementSpec, *, max_active: int = 14,
                wear_aware: Optional[bool] = None) -> zengine.ZoneEngine:
    return zengine.ZoneEngine(flash, zone, spec, max_active=max_active,
                              wear_aware=wear_aware)


def dlwa_program(eng: zengine.ZoneEngine, *, occupancy: float,
                 n_zones: Optional[int] = None, zone_base: int = 0,
                 zone_pages: Optional[int] = None) -> np.ndarray:
    """Encode :func:`dlwa_benchmark` as an op program.

    ``zone_base`` offsets the zones touched (the fleet layer namespaces
    tenants into disjoint zone ranges); ``zone_pages`` overrides the
    capacity occupancy is computed against (a fleet superzone's logical
    capacity, or a ``DynConfig`` effective geometry)."""
    cfg = eng.cfg
    n_zones = n_zones or min(8, cfg.n_zones)
    cap = zone_pages or cfg.zone_pages
    pages = max(1, int(round(cap * occupancy)))
    pages = min(pages, cap)
    rows = []
    for z in range(zone_base, zone_base + n_zones):
        rows.append((zengine.OP_WRITE, z, pages, zengine.F_HOST))
        rows.append((zengine.OP_FINISH, z, 0, 0))
    return zengine.encode_program(rows)


def _dlwa_metrics(host: int, dummy: int, occupancy: float,
                  n_zones: int) -> Dict[str, float]:
    return {
        "occupancy": occupancy,
        "host_pages": float(host),
        "dummy_pages": float(dummy),
        "dummy_pages_per_zone": dummy / n_zones,
        "dlwa": (host + dummy) / host if host else 1.0,
    }


def dlwa_benchmark_engine(eng: zengine.ZoneEngine, *, occupancy: float,
                          n_zones: Optional[int] = None) -> Dict[str, float]:
    """:func:`dlwa_benchmark` as one ``lax.scan`` (fresh device state)."""
    n_zones = n_zones or min(8, eng.cfg.n_zones)
    prog = dlwa_program(eng, occupancy=occupancy, n_zones=n_zones)
    state, _ = eng.run(eng.init_state(), prog)
    return _dlwa_metrics(int(state.host_pages), int(state.dummy_pages),
                         occupancy, n_zones)


def dlwa_sweep_engine(eng: zengine.ZoneEngine,
                      occupancies: Sequence[float],
                      *, n_zones: Optional[int] = None
                      ) -> List[Dict[str, float]]:
    """A whole occupancy sweep in ONE vmapped scan: every program has the
    same shape (pages varies per row), so the sweep batches cleanly."""
    n_zones = n_zones or min(8, eng.cfg.n_zones)
    programs = np.stack([
        dlwa_program(eng, occupancy=o, n_zones=n_zones)
        for o in occupancies])
    states, _ = eng.run_batch(eng.init_state(), programs)
    hosts = np.asarray(states.host_pages)
    dummies = np.asarray(states.dummy_pages)
    return [_dlwa_metrics(int(hosts[k]), int(dummies[k]), occ, n_zones)
            for k, occ in enumerate(occupancies)]


def _op_traces(eng: zengine.ZoneEngine, program: np.ndarray, trace
               ) -> List[Optional[IOTrace]]:
    """Per-op IOTraces of an executed program (None for no-IO ops)."""
    wp_b = np.asarray(trace.wp_before)
    wp_a = np.asarray(trace.wp_after)
    dummy = np.asarray(trace.dummy_delta)
    elems = np.asarray(trace.elems)
    cols = np.asarray(trace.cols)
    out: List[Optional[IOTrace]] = []
    for i in range(len(program)):
        s = eng.op_stream(int(program[i, 0]), int(wp_b[i]), int(wp_a[i]),
                          int(dummy[i]), elems[i], cols[i])
        out.append(None if s is None else IOTrace(s[0], s[1], s[2]))
    return out


def interference_program(eng: zengine.ZoneEngine, *, concurrency: int,
                         fill_occupancy: float = 0.4,
                         host_pages_per_zone: Optional[int] = None,
                         zone_base: int = 0,
                         zone_pages: Optional[int] = None) -> np.ndarray:
    """Fused finish+host-write program (victim fills, host writes, victim
    FINISHes) -- the exact op order of :func:`interference_benchmark`.
    ``zone_base`` / ``zone_pages`` as in :func:`dlwa_program`."""
    cfg = eng.cfg
    cap = zone_pages or cfg.zone_pages
    fill = max(1, int(round(cap * fill_occupancy)))
    hpz = host_pages_per_zone or fill
    rows = []
    b = zone_base
    for z in range(b, b + concurrency):                    # victims fill
        rows.append((zengine.OP_WRITE, z, fill, zengine.F_HOST))
    for z in range(b + concurrency, b + 2 * concurrency):  # host writers
        rows.append((zengine.OP_WRITE, z, hpz, zengine.F_HOST))
    for z in range(b, b + concurrency):                    # victims FINISH
        rows.append((zengine.OP_FINISH, z, 0, 0))
    return zengine.encode_program(rows)


def interference_benchmark_engine(eng: zengine.ZoneEngine, *,
                                  concurrency: int,
                                  fill_occupancy: float = 0.4,
                                  host_pages_per_zone: Optional[int] = None
                                  ) -> Dict[str, float]:
    """:func:`interference_benchmark` via one scan + one stream rebuild;
    timing uses the same :func:`repro.core.timing.run_trace` merge."""
    prog = interference_program(
        eng, concurrency=concurrency, fill_occupancy=fill_occupancy,
        host_pages_per_zone=host_pages_per_zone)
    state, trace = eng.run(eng.init_state(), prog)
    streams = _op_traces(eng, prog, trace)
    host_traces = [t for t in streams[concurrency: 2 * concurrency]
                   if t is not None]
    finish_traces = [t for t in streams[2 * concurrency:]
                     if t is not None and len(t.luns)]
    base = timing.run_trace(eng.flash, host_traces)
    base_tp = sum(base[f"owner{i}_throughput_pages_s"]
                  for i in range(len(host_traces)))
    cont = timing.run_trace(eng.flash, host_traces + finish_traces)
    cont_tp = sum(cont[f"owner{i}_throughput_pages_s"]
                  for i in range(len(host_traces)))
    factor = base_tp / cont_tp if cont_tp else float("inf")
    return {
        "concurrency": float(concurrency),
        "baseline_pages_s": base_tp,
        "contended_pages_s": cont_tp,
        "interference": factor,
        "dummy_pages": float(sum(len(t.luns) for t in finish_traces)),
    }


def write_program(eng: zengine.ZoneEngine, *, request_kib: int,
                  n_jobs: int, mib_per_job: int = 16, zone_base: int = 0,
                  zone_pages: Optional[int] = None) -> np.ndarray:
    """Encode :func:`write_benchmark`'s sequential-writer jobs (one
    dedicated zone each) as an op program.  ``zone_base`` /
    ``zone_pages`` as in :func:`dlwa_program`."""
    cfg = eng.cfg
    cap = zone_pages or cfg.zone_pages
    pages_per_req = max(1, request_kib * 1024 // eng.flash.page_bytes)
    reqs_per_job = max(1, mib_per_job * 1024 * 1024
                       // (pages_per_req * eng.flash.page_bytes))
    total_pages = min(pages_per_req * reqs_per_job, cap)
    return zengine.encode_program(
        [(zengine.OP_WRITE, zone_base + j, total_pages, zengine.F_HOST)
         for j in range(n_jobs)])


def write_benchmark_engine(eng: zengine.ZoneEngine, *, request_kib: int,
                           n_jobs: int, mib_per_job: int = 16
                           ) -> Dict[str, float]:
    """:func:`write_benchmark` as an op program + one stream rebuild."""
    prog = write_program(eng, request_kib=request_kib, n_jobs=n_jobs,
                         mib_per_job=mib_per_job)
    state, trace = eng.run(eng.init_state(), prog)
    traces = [t for t in _op_traces(eng, prog, trace) if t is not None]
    stats = timing.run_trace(eng.flash, traces)
    return {
        "request_kib": float(request_kib),
        "n_jobs": float(n_jobs),
        "pages": float(stats["n"]),
        "bandwidth_mib_s": timing.write_bandwidth_mib_s(eng.flash, stats),
        "makespan_s": stats["makespan_s"],
    }
