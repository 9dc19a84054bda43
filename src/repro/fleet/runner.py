"""Batched fleet execution: T tenants x N devices x K configs, one dispatch.

A fleet *lane* is one (config, member-device) pair: a width-5 op program
(see :mod:`repro.fleet.tenants`) plus a per-lane
:class:`repro.core.engine.DynConfig` selecting the member's effective
zone geometry / allocator on the shared padded static
:class:`~repro.core.engine.EngineConfig`.  :func:`run_fleet` stacks all
lanes and executes them through ONE ``run_programs`` dispatch (scans
over the lanes' rows, in lane groups on the TPU), then scores latency
with ONE :func:`repro.core.timing.simulate_fleet_ops` dispatch -- no
per-config or per-device Python loops on the hot path.

Metric units: page counters count flash pages, ``erase_delta`` counts
erase-block erasures, times are seconds.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core import engine as zengine
from repro.core import timing
from repro.core.elements import union_grid_mask
from repro.core.engine import DeviceState, DynConfig, ZoneEngine
from repro.fleet.tenants import TENANT_COL
from repro.obs.profile import count, span


class Rebuilds(NamedTuple):
    """The member rebuilds of a fleet batch (the ``rebuilds`` of
    :func:`repro.fleet.search.fleet_batch` when a config fails): what
    the timing and the recovery rollups read.

    Each array with a failure has one *failure instant*: the latest
    completion of a row its lanes issued before the failure.  No later
    row of the array starts before it, and a chunk appended to the
    replacement waits for the survivor reads it is computed from.
    """

    tenant: int          # tag of the rebuild rows (parity tag + 1)
    marks: np.ndarray    # (L,) rows issued before the failure; -1 on
                         #   lanes of healthy arrays and pad lanes
    group: np.ndarray    # (L,) the array (config) each lane belongs to
    waits: np.ndarray    # (K, 4) lane, row, source lane, source row: a
                         #   row issued once its source row completed


class FinalCounters(NamedTuple):
    """Host copy of the final-state fields the rollups read, ``(L,
    ...)`` numpy leaves (``elem_wear`` keeps the padded element axis
    and its scratch slot).  One lane's slice (:meth:`lane`) carries the
    counters :meth:`ZoneEngine.metrics` reads."""

    elem_wear: np.ndarray
    host_pages: np.ndarray
    dummy_pages: np.ndarray
    block_erases: np.ndarray
    alloc_calls: np.ndarray
    n_active: np.ndarray

    def lane(self, lane: int) -> "FinalCounters":
        return FinalCounters(*(x[lane] for x in self))


@dataclasses.dataclass
class FleetResult:
    """Per-lane outputs of one batched fleet dispatch.

    Lane axis ``L`` = flattened (config, device); op axis is the padded
    program length.  ``tenants`` holds the width-5 tenant column;
    parity appends carry ``parity_tenant``, a member rebuild's rows
    ``rebuilds.tenant`` (``rebuilds`` is None when no lane rebuilds);
    NOP padding moves 0 pages and is ignored by every rollup.

    ``states`` (and ``telemetry``, ``dyn``) stay on the device; every
    other array is numpy.  The rollups read the final state through
    :meth:`final`, a host copy fetched once per result.
    """

    programs: np.ndarray     # (L, n_ops, 5) i32
    states: DeviceState      # stacked pytree, leading axis L
    ok: np.ndarray           # (L, n_ops) bool  per-op legality
    host_delta: np.ndarray   # (L, n_ops) host pages moved by each op
    dummy_delta: np.ndarray  # (L, n_ops) dummy (FINISH-pad) pages
    erase_delta: np.ndarray  # (L, n_ops) block erasures
    pages: np.ndarray        # (L, n_ops) pages the op physically moved
                             #   (writes + FINISH padding + READ xfers)
    cols: np.ndarray         # (L, n_ops, P) zone column -> LUN per op
    completions: np.ndarray  # (L, n_ops) op completion time (s)
    latencies: np.ndarray    # (L, n_ops) closed-loop op latency (s)
    makespans: np.ndarray    # (L,) lane makespan (s)
    n_tenants: int           # real tenants (parity tag excluded)
    parity_tenant: int
    elem_mask: Optional[np.ndarray] = None  # (L, n_elements) real elements
    #: per-lane telemetry stack (repro.obs TelemetryState with (L, ...)
    #: leaves) when the dispatch ran with obs=ObsConfig(...), else None
    telemetry: Optional[object] = None
    #: the dispatch's static config + per-lane DynConfig (when known):
    #: what lets assert_all_ok replay a failing lane through the
    #: repro.check verifier and name the predicted error class
    cfg: Optional[zengine.EngineConfig] = None
    dyn: Optional[DynConfig] = None
    rebuilds: Optional[Rebuilds] = None
    _final: Optional[FinalCounters] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def tenants(self) -> np.ndarray:
        return self.programs[:, :, TENANT_COL]

    def final(self) -> FinalCounters:
        """The final-state fields the rollups read, on the host: whole
        fields in one device read on first use (counted as
        ``rollup.device_reads``, timed as ``fleet.rollup``), then
        cached."""
        if self._final is None:
            with span("fleet.rollup"):
                count("rollup.device_reads", 1)
                self._final = jax.device_get(FinalCounters(
                    *(getattr(self.states, f)
                      for f in FinalCounters._fields)))
        return self._final

    def lane_wear(self, eng: ZoneEngine) -> np.ndarray:
        """(L, n_elements) element wear (erase counts) per lane, over
        the full padded static element axis (see ``elem_mask`` /
        :meth:`pooled_wear` for the per-lane real subset)."""
        n = eng.cfg.n_elements
        return np.asarray(self.final().elem_wear[:, :n], dtype=np.int64)

    def pooled_wear(self, eng: ZoneEngine, lanes: np.ndarray
                    ) -> np.ndarray:
        """1-D element wear pooled over ``lanes``, restricted to each
        lane's *real* elements.  A union-config lane only populates its
        member spec's cells of the padded element grid; ``elem_mask``
        (derived from the dispatch's per-lane ``DynConfig``) excludes
        the never-allocated padding so wear statistics match a device
        built with the member spec outright."""
        with span("fleet.rollup"):
            w = self.final().elem_wear[lanes, :eng.cfg.n_elements].astype(
                np.int64)
            if self.elem_mask is None:
                return w.reshape(-1)
            return w[self.elem_mask[lanes]]

    def tenant_pages(self, lanes: np.ndarray) -> Dict[int, int]:
        """Host pages per tenant summed over ``lanes`` (parity under
        ``parity_tenant``)."""
        t = self.tenants[lanes].reshape(-1)
        h = self.host_delta[lanes].reshape(-1)
        return {int(k): int(h[t == k].sum())
                for k in range(self.n_tenants)} | {
                    self.parity_tenant:
                    int(h[t == self.parity_tenant].sum())}

    def tenant_p99_latency(self, lanes: np.ndarray) -> Dict[int, float]:
        """p99 closed-loop op latency per real tenant over ``lanes``
        (0.0 for a tenant with no executed ops there)."""
        t = self.tenants[lanes].reshape(-1)
        lat = self.latencies[lanes].reshape(-1)
        act = self.pages[lanes].reshape(-1) > 0
        out = {}
        for k in range(self.n_tenants):
            sel = act & (t == k)
            out[k] = float(np.percentile(lat[sel], 99)) if sel.any() else 0.0
        return out

    def tenant_class_report(self, lanes: Optional[np.ndarray] = None,
                            names: Optional[List[str]] = None
                            ) -> Dict[str, Dict[str, float]]:
        """Per-tenant-class latency predictability over ``lanes`` (all
        lanes by default).

        When the tenant column carries *traffic classes* (the trace
        compiler's class-tagged dispatches: wal/flush/compact,
        ckpt/log, admit/hit), this is the paper-style per-stream
        rollup: op and page counts, closed-loop latency p50/p99/max,
        and ``p99_over_p50`` -- the predictability ratio a
        well-isolated class keeps near 1.  ``names`` labels classes in
        tag order; unnamed tags keep their number."""
        with span("fleet.rollup"):
            lanes = (np.arange(len(self.programs)) if lanes is None
                     else np.asarray(lanes))
            t = self.tenants[lanes].reshape(-1)
            lat = self.latencies[lanes].reshape(-1)
            pages = self.pages[lanes].reshape(-1)
            host = self.host_delta[lanes].reshape(-1)
            act = (self.programs[lanes][:, :, 0].reshape(-1)
                   != zengine.OP_NOP) & self.ok[lanes].reshape(-1)
            out: Dict[str, Dict[str, float]] = {}
            for k in range(self.n_tenants):
                name = (names[k] if names is not None and k < len(names)
                        else str(k))
                sel = act & (t == k)
                if not sel.any():
                    out[name] = dict.fromkeys((
                        "ops", "pages", "host_pages", "mean_latency_s",
                        "p50_latency_s", "p99_latency_s", "max_latency_s",
                        "p99_over_p50"), 0.0)
                    continue
                l_k = lat[sel]
                p50 = float(np.percentile(l_k, 50))
                p99 = float(np.percentile(l_k, 99))
                out[name] = {
                    "ops": float(sel.sum()),
                    "pages": float(pages[sel].sum()),
                    "host_pages": float(host[sel].sum()),
                    "mean_latency_s": float(l_k.mean()),
                    "p50_latency_s": p50,
                    "p99_latency_s": p99,
                    "max_latency_s": float(l_k.max()),
                    "p99_over_p50": p99 / p50 if p50 > 0 else 0.0,
                }
            return out


def run_fleet(eng: ZoneEngine, programs: np.ndarray, *,
              dyn: Optional[DynConfig] = None, n_tenants: int = 1,
              parity_tenant: Optional[int] = None,
              rebuilds: Optional[Rebuilds] = None, obs=None,
              profiler=None) -> FleetResult:
    """Execute ``(L, n_ops, 5)`` fleet lanes in one batched dispatch.

    ``dyn`` (optional) must hold ``(L,)`` leaves (``engine.stack_dyn``)
    -- the heterogeneous-geometry / allocator axis.  Timing is the
    op-granular :func:`~repro.core.timing.simulate_fleet_ops` model:
    each executed op occupies its zone's LUN columns for
    ``ceil(pages / P) * (t_prog + t_xfer)`` seconds; deferred-erase
    latency is not modeled (it is tracked as ``erase_delta`` instead).
    Tenant tags run up to ``parity_tenant``.  A batch holding member
    rebuilds passes ``rebuilds``: their rows are one more closed-loop
    stream per lane, and the clock runs three times (see
    :func:`_rebuild_clock`); healthy batches keep their timing shape.

    ``obs`` (a ``repro.obs.ObsConfig``) threads the in-scan telemetry
    recorder through the dispatch; the result then carries per-lane
    histogram stacks in ``telemetry``.  ``profiler`` (a
    ``repro.obs.Profiler``, else the current one) splits the call into
    ``fleet.engine`` / ``fleet.timing`` / ``fleet.decode`` sections
    (outputs are blocked on inside the first two when they are timed,
    so their wall times hold the device work).
    """
    programs = np.asarray(programs, dtype=np.int32)
    if programs.ndim != 3 or programs.shape[-1] <= TENANT_COL:
        raise ValueError(f"want (L, n_ops, 5) programs, got "
                         f"{programs.shape}")
    if parity_tenant is None:
        parity_tenant = n_tenants
    # the lane groups run_programs steps through (engine.lane_group_width)
    n_lanes, n_rows = programs.shape[:2]
    width = zengine.lane_group_width(n_lanes, jax.default_backend())
    groups = -(-n_lanes // width)
    with span("fleet.engine", profiler) as timed:
        count("engine.groups", groups)
        count("engine.lane_steps", groups * width * n_rows)
        out = eng.run_batch(eng.init_state(), programs, dyn, obs=obs)
        states, trace = out[0], out[1]
        alloc_steps = zengine.group_alloc_steps(trace.opens, width)
        telemetry = out[2] if obs is not None else None
        elem_mask = None
        if dyn is not None:
            # each lane's real elements on the (possibly union-padded)
            # static grid -- union lanes must exclude the padding cells
            # from the wear rollups.  Read from the inputs while the
            # device runs the lanes, timed or not.
            elem_mask = union_grid_mask(
                eng.cfg.n_elements, eng.cfg.per_group,
                np.asarray(dyn.n_elements), np.asarray(dyn.per_group))
        if timed is not None:
            jax.block_until_ready(states)

    with span("fleet.timing", profiler) as timed:
        cols, wp_b, wp_a, dummy, alloc_steps = jax.device_get((
            trace.cols, trace.wp_before, trace.wp_after, trace.dummy_delta,
            alloc_steps))
        count("engine.alloc_steps", int(alloc_steps.sum()))
        op = programs[:, :, 0]
        # pages the op physically moved: write advance, FINISH padding
        # (RESET rewinds wp without moving pages -> clip), READ
        # transfers (the n_pages column; reads never advance wp)
        pages = (np.maximum(wp_a - wp_b, 0)
                 + np.where(op == zengine.OP_FINISH, dummy, 0)
                 + np.where(op == zengine.OP_READ, programs[:, :, 2], 0))
        # per-op page service time: reads pay t_read, everything
        # page-moving else programs flash
        t_page = np.where(
            op == zengine.OP_READ,
            np.float32(eng.flash.t_read + eng.flash.t_xfer),
            np.float32(eng.flash.t_prog + eng.flash.t_xfer))
        args = (cols, pages.astype(np.int32), programs[:, :, TENANT_COL],
                t_page, eng.flash.n_luns)
        if rebuilds is None:
            completions, latencies, makespans = timing.simulate_fleet_ops(
                *args, parity_tenant + 1)
        else:
            completions, latencies, makespans = _rebuild_clock(
                *args, rebuilds)
        if timed is not None:
            jax.block_until_ready(completions)
    with span("fleet.decode", profiler):
        return _decode_fleet(programs, states, trace, dummy, pages, cols,
                             completions, latencies, makespans,
                             n_tenants, parity_tenant, elem_mask,
                             telemetry, eng.cfg, dyn, rebuilds)


def _rebuild_clock(cols, pages, tenants, t_page, n_luns: int,
                   rebuilds: Rebuilds):
    """``simulate_fleet_ops`` over a batch holding member rebuilds.

    Lanes keep separate clocks, so the failure's order across lanes is
    imposed by ready times, in three runs of the clock: the first
    times the rows issued before each failure (nothing before them
    changes later) and so each array's failure instant; the second
    holds every later row of the array to that instant, which times
    the survivors (no row of theirs waits for another lane); the third
    holds each chunk appended to the replacement until the survivor
    reads it is computed from have completed."""
    n_tenants = rebuilds.tenant + 1
    marks, group = rebuilds.marks, rebuilds.group
    failed = marks >= 0
    after = np.arange(pages.shape[1])[None, :] >= marks[:, None]
    ready = np.zeros(pages.shape, np.float32)
    done = np.asarray(timing.simulate_fleet_ops(
        cols, pages, tenants, t_page, n_luns, n_tenants, ready)[0])
    instant = np.zeros(int(group.max(initial=-1)) + 1, np.float32)
    np.maximum.at(instant, group[failed],
                  np.where(after, np.float32(0), done)[failed].max(axis=1))
    ready = np.where(failed[:, None] & after,
                     instant[np.maximum(group, 0)][:, None],
                     np.float32(0)).astype(np.float32)
    done = np.asarray(timing.simulate_fleet_ops(
        cols, pages, tenants, t_page, n_luns, n_tenants, ready)[0])
    lane, row, src_lane, src_row = np.asarray(rebuilds.waits).T
    np.maximum.at(ready, (lane, row), done[src_lane, src_row])
    return timing.simulate_fleet_ops(cols, pages, tenants, t_page, n_luns,
                                     n_tenants, ready)


def _decode_fleet(programs, states, trace, dummy, pages, cols, completions,
                  latencies, makespans, n_tenants, parity_tenant,
                  elem_mask, telemetry, cfg=None, dyn=None,
                  rebuilds=None) -> FleetResult:
    return FleetResult(
        programs=programs,
        states=states,
        ok=np.asarray(trace.ok),
        host_delta=np.asarray(trace.host_delta),
        dummy_delta=dummy,
        erase_delta=np.asarray(trace.erase_delta),
        pages=pages,
        cols=cols,
        completions=np.asarray(completions),
        latencies=np.asarray(latencies),
        makespans=np.asarray(makespans),
        n_tenants=n_tenants,
        parity_tenant=parity_tenant,
        elem_mask=elem_mask,
        telemetry=telemetry,
        cfg=cfg,
        dyn=dyn,
        rebuilds=rebuilds,
    )


def config_report(res: FleetResult, eng: ZoneEngine,
                  lanes: np.ndarray) -> Dict[str, float]:
    """Roll one config's member lanes up to the paper's fleet metrics.

    * ``dlwa``: array-level -- every page the fleet programs (host data
      + parity + FINISH padding) per host data page;
    * ``wear_cv`` / ``max_wear``: spread of element wear pooled over
      all members (the wear-leveling objective, paper Fig. 7c);
    * ``p99_latency_s``: worst real tenant's p99 closed-loop latency;
    * ``makespan_s``: slowest member (the fleet completes a stripe only
      when every chunk is durable).

    A config whose member failed and was rebuilt (its lanes' marks in
    ``res.rebuilds``) also gets, timed as ``fleet.recover``:
    ``recover_s`` (the rebuild's last completion less the failure
    instant, the latest completion of a row issued before the failure
    over its lanes; 0 without rebuild rows),
    ``rebuild_pages`` (pages the rebuild appended to the replacement),
    ``tenant<k>_p99_after_failure_s`` per real tenant and
    ``member<d>_dlwa`` per member (its host + padding pages over its
    host pages, parity and rebuild appends included).  The keys above
    stay computed over the lanes as they ran: rebuild appends count as
    host pages of the replacement, and the failed member's rows from
    before the failure are gone with it.
    """
    with span("fleet.rollup"):
        lanes = np.asarray(lanes)
        t = res.tenants[lanes]
        host = int(res.host_delta[lanes][t != res.parity_tenant].sum())
        par = int(res.host_delta[lanes][t == res.parity_tenant].sum())
        dummy = int(res.dummy_delta[lanes].sum())
        erases = int(res.erase_delta[lanes].sum())
        wear = res.pooled_wear(eng, lanes)
        mean_w = float(wear.mean()) if wear.size else 0.0
        p99 = res.tenant_p99_latency(lanes)
        out = {
            "host_pages": float(host),
            "parity_pages": float(par),
            "dummy_pages": float(dummy),
            "dlwa": (host + par + dummy) / host if host else 1.0,
            "block_erases": float(erases),
            "max_wear": float(wear.max()) if wear.size else 0.0,
            "wear_cv": (float(wear.std() / mean_w) if mean_w > 0
                        else 0.0),
            "p99_latency_s": max(p99.values()) if p99 else 0.0,
            "makespan_s": float(res.makespans[lanes].max()),
            "ops_ok": float(res.ok[lanes].sum()),
        }
        if (res.rebuilds is not None
                and (res.rebuilds.marks[lanes] >= 0).any()):
            with span("fleet.recover"):
                out.update(_recovery(res, lanes, t))
        return out


def _recovery(res: FleetResult, lanes: np.ndarray,
              t: np.ndarray) -> Dict[str, float]:
    """``config_report``'s time to recover, post-failure tenant p99s
    and per-member DLWA."""
    done = res.completions[lanes]
    after = (np.arange(done.shape[1])[None, :]
             >= res.rebuilds.marks[lanes][:, None])
    rb = t == res.rebuilds.tenant
    out: Dict[str, float] = {
        "recover_s": 0.0,
        "rebuild_pages": float(int(res.host_delta[lanes][rb].sum()))}
    if rb.any():
        gap = done[rb].max() - done[~after].max(initial=np.float32(0))
        out["recover_s"] = float(max(gap, np.float32(0)))
    lat = res.latencies[lanes]
    act = after & (res.pages[lanes] > 0)
    for k in range(res.n_tenants):
        sel = act & (t == k)
        out[f"tenant{k}_p99_after_failure_s"] = (
            float(np.percentile(lat[sel], 99)) if sel.any() else 0.0)
    final = res.final()
    host, dummy = final.host_pages[lanes], final.dummy_pages[lanes]
    for d, (h, pad) in enumerate(zip(host.tolist(), dummy.tolist())):
        out[f"member{d}_dlwa"] = (h + pad) / h if h else 1.0
    return out


def dispatch_cost(res: FleetResult) -> int:
    """Scanned ``(lane, op)`` cells of one dispatch -- lanes times the
    padded program length, NOP padding included.  This is the raw
    compute a batched evaluator invocation paid (every lane scans the
    full padded op axis), the unit the search-budget ledger in
    :class:`repro.fleet.search.Evaluator` accumulates."""
    return int(res.programs.shape[0] * res.programs.shape[1])


def real_op_count(res: FleetResult) -> int:
    """Non-NOP ops across all lanes (the work that moved state)."""
    return int((res.programs[:, :, 0] != zengine.OP_NOP).sum())


def assert_all_ok(res: FleetResult, lanes: Optional[np.ndarray] = None
                  ) -> None:
    """Raise if any *real* op (non-NOP) was illegal -- a mis-built
    fleet program (overflow, active-zone limit) should fail loudly in
    tests and benchmarks, not skew metrics silently.

    When the result carries its dispatch config (``res.cfg`` /
    ``res.dyn``, populated by :func:`run_fleet`), the first failing op
    is replayed through the :mod:`repro.check` verifier and the
    exception names the op kind, zone, and predicted error class with
    the shim's message -- not just the raw row."""
    with span("fleet.check"):
        sel = slice(None) if lanes is None else lanes
        real = res.programs[sel, :, 0] != zengine.OP_NOP
        bad = real & ~res.ok[sel]
        if not bad.any():
            return
    lane, idx = np.argwhere(bad)[0]
    row = res.programs[sel][lane, idx]
    msg = (f"illegal op at lane {lane} index {idx}: {row.tolist()}")
    if res.cfg is not None:
        # absolute lane on the dispatch axis (``lanes`` may be a subset)
        abs_lane = int(np.arange(len(res.programs))[sel][lane])
        from repro.check import explain_op
        stacked = (res.dyn is not None
                   and np.asarray(res.dyn.zone_pages).ndim > 0)
        v = explain_op(res.cfg, res.programs[abs_lane], int(idx),
                       res.dyn, lane=abs_lane if stacked else None)
        if not v.ok:
            msg = (f"illegal {v.op_name} at lane {lane} index {idx} "
                   f"(zone {v.zone}): predicted error class "
                   f"'{v.error}' -- {v.message}; row {row.tolist()}")
    raise AssertionError(msg)
