"""FEMU-style timing model as a vectorized JAX scan (hardware adaptation).

ConfZNS++/FEMU advance an event-driven clock per flash channel and LUN; we
keep exactly the resources and latencies (program/read/erase/channel
transfer) but execute the request stream as a ``jax.lax.scan`` over
per-resource *busy clocks*:

    start(req)  = max(channel_free[ch], lun_free[lun])
    channel_free[ch] = start + t_xfer
    lun_free[lun]    = start + t_xfer + t_op

This reproduces what the paper measures -- throughput saturation across
parallel units (Fig. 9) and FINISH-vs-host interference (Fig. 4b/7d,
Table 3) -- without NVMe protocol details.  Streams from different actors
(host writers, device FINISH padding) are merged round-robin to model
concurrent submission queues.

Three granularities, coarse to fine:

* :func:`simulate_fleet_ops` -- whole zone ops as single requests, one
  vmapped scan over thousands of (config x device) lanes; the fleet
  allocator search's latency objective.
* :func:`simulate_fleet` / :func:`run_fleet_trace` -- page-granular,
  one vmapped scan per fleet (devices are independent hardware).
* :func:`simulate` / :func:`run_trace` -- page-granular single device,
  the paper-faithful model behind the reported figures.

Units: times in seconds, requests in flash pages (ops/luns/channels are
int32 indexes).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import IOTrace
from repro.core.geometry import FlashGeometry

OP_WRITE, OP_READ, OP_ERASE = 0, 1, 2
_OP_CODE = {"write": OP_WRITE, "read": OP_READ, "erase": OP_ERASE}


@functools.partial(jax.jit, static_argnames=("n_luns", "n_channels"))
def simulate(ops: jax.Array, luns: jax.Array, channels: jax.Array,
             t_op: jax.Array, t_xfer: jax.Array,
             n_luns: int, n_channels: int) -> Tuple[jax.Array, jax.Array]:
    """Scan a request stream through per-LUN/per-channel busy clocks.

    Args:
      ops:      (n,) int32 op codes (indexes ``t_op``).
      luns:     (n,) int32 LUN of each request.
      channels: (n,) int32 channel of each request.
      t_op:     (3,) float32 [t_prog, t_read, t_erase].
      t_xfer:   () float32 channel transfer time.

    Returns:
      (completion_times (n,), makespan ()).
    """
    def step(carry, req):
        lun_free, ch_free = carry
        op, lun, ch = req
        start = jnp.maximum(lun_free[lun], ch_free[ch])
        done_xfer = start + t_xfer
        done = done_xfer + t_op[op]
        lun_free = lun_free.at[lun].set(done)
        ch_free = ch_free.at[ch].set(done_xfer)
        return (lun_free, ch_free), done

    init = (jnp.zeros(n_luns, jnp.float32),
            jnp.zeros(n_channels, jnp.float32))
    (lun_free, _), completions = jax.lax.scan(
        step, init, (ops, luns, channels))
    return completions, jnp.max(lun_free)


@functools.partial(jax.jit, static_argnames=("n_luns", "n_channels"))
def simulate_fleet(ops: jax.Array, luns: jax.Array, channels: jax.Array,
                   valid: jax.Array, t_op: jax.Array, t_xfer: jax.Array,
                   n_luns: int, n_channels: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Batched-device :func:`simulate`: one compiled scan for a fleet.

    Devices are independent hardware, so their busy clocks never interact;
    ``jax.vmap`` over a leading device axis runs all per-device scans in
    one XLA program instead of N sequential dispatches.  Streams of
    unequal length are right-padded; ``valid`` masks padding out of both
    the clocks and the completions.

    Args:
      ops/luns/channels: (n_dev, n) int32, right-padded per device.
      valid:             (n_dev, n) bool, False on padding.
      t_op:              (3,) float32 [t_prog, t_read, t_erase].
      t_xfer:            () float32 channel transfer time.

    Returns:
      (completion_times (n_dev, n) with 0 on padding, makespans (n_dev,)).
    """
    def one_device(ops_d, luns_d, chans_d, valid_d):
        def step(carry, req):
            lun_free, ch_free = carry
            op, lun, ch, ok = req
            start = jnp.maximum(lun_free[lun], ch_free[ch])
            done_xfer = start + t_xfer
            done = done_xfer + t_op[op]
            lun_free = lun_free.at[lun].set(
                jnp.where(ok, done, lun_free[lun]))
            ch_free = ch_free.at[ch].set(
                jnp.where(ok, done_xfer, ch_free[ch]))
            return (lun_free, ch_free), jnp.where(ok, done, 0.0)

        init = (jnp.zeros(n_luns, jnp.float32),
                jnp.zeros(n_channels, jnp.float32))
        (lun_free, _), completions = jax.lax.scan(
            step, init, (ops_d, luns_d, chans_d, valid_d))
        return completions, jnp.max(lun_free)

    return jax.vmap(one_device)(ops, luns, channels, valid)


@functools.partial(jax.jit, static_argnames=("n_luns", "n_tenants"))
def simulate_fleet_ops(cols: jax.Array, pages: jax.Array,
                       tenants: jax.Array, t_page: jax.Array,
                       n_luns: int, n_tenants: int,
                       ready: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Op-granular fleet timing: one batched scan over whole zone ops.

    Where :func:`simulate` advances busy clocks *per page*, this models
    each executed op (a chunk write, FINISH padding burst, parity
    append) as one request occupying all of its zone's LUN columns for
    ``ceil(pages / P) * t_page`` seconds -- the round-robin stripe means
    every column programs ``ceil(pages/P)`` pages back to back.  It is
    the coarse, fully-batched objective the fleet allocator search
    scores thousands of lanes with in a single dispatch; the
    page-granular :func:`run_trace` remains the paper-faithful model
    for reported figures.

    Tenant latency is closed-loop: a tenant issues its next op when its
    previous op completes, so ``latency = completion - previous
    completion of the same tenant`` (queueing + service).  ``ready``
    gives an op an earliest issue time (an event on another lane it
    waits for, such as a member failure or the survivor reads a rebuilt
    chunk is computed from): it then starts no earlier, and its latency
    runs from the later of the two issue times.

    Args:
      cols:    (n_lanes, n_ops, P) int32 zone column -> LUN of each op
               (from ``OpTrace.cols``).
      pages:   (n_lanes, n_ops) int32 pages the op moved (0 = skip).
      tenants: (n_lanes, n_ops) int32 tenant tag in ``[0, n_tenants)``.
      t_page:  () f32 seconds per page program+transfer, or
               (n_lanes, n_ops) f32 per-op page cost (the array runner
               prices READ rows at ``t_read + t_xfer``).
      n_luns/n_tenants: static sizes.
      ready:   optional (n_lanes, n_ops) f32 earliest issue time per op.

    Returns:
      (completions (n_lanes, n_ops) f32 with 0 on skipped ops,
       latencies (n_lanes, n_ops) f32, makespans (n_lanes,) f32).
    """
    P = cols.shape[-1]
    # each op's service time, before the scan: an integer ceil (a float
    # divide need not round alike on every backend) times the page
    # cost.  Outside the scan no backend can fuse the multiply with the
    # clock's add into one FMA, so every backend rounds both, as the
    # model (and its numpy reference) does
    dur = ((pages + P - 1) // P).astype(jnp.float32) * jnp.asarray(
        t_page, jnp.float32)

    def one_lane(cols_l, pages_l, ten_l, dur_l, *ready_l):
        def step(carry, x):
            lun_free, ten_done = carry
            c, pg, t, dur, *rdy = x
            active = pg > 0
            # an op is issued when its tenant has completed its previous
            # op (closed loop), and not before its ready time; it starts
            # when its LUN columns free up too
            issued = ten_done[t] if not rdy else jnp.maximum(
                ten_done[t], rdy[0])
            start = jnp.maximum(
                jnp.max(jnp.where(active, lun_free[c], 0.0)), issued)
            done = start + dur
            lat = jnp.where(active, done - issued, 0.0)
            lun_free = lun_free.at[c].set(
                jnp.where(active, done, lun_free[c]))
            ten_done = ten_done.at[t].set(
                jnp.where(active, done, ten_done[t]))
            return (lun_free, ten_done), (jnp.where(active, done, 0.0), lat)

        init = (jnp.zeros(n_luns, jnp.float32),
                jnp.zeros(n_tenants, jnp.float32))
        (lun_free, _), (done, lat) = jax.lax.scan(
            step, init, (cols_l, pages_l, ten_l, dur_l, *ready_l))
        return done, lat, jnp.max(lun_free)

    extra = () if ready is None else (jnp.asarray(ready, jnp.float32),)
    return jax.vmap(one_lane)(cols, pages, tenants, dur, *extra)


def run_fleet_trace(flash: FlashGeometry,
                    device_traces: Sequence[Sequence[IOTrace]],
                    *, interleave: bool = True) -> dict:
    """Simulate per-device trace bundles in one vmapped scan.

    ``device_traces[i]`` holds device ``i``'s concurrent streams (host
    data chunks, parity appends routed to it, FINISH padding); each
    device's streams are merged round-robin (cross-device merge for
    parity traffic) exactly as :func:`run_trace` would, then all devices
    advance together under :func:`simulate_fleet`.

    Returns per-device makespans/throughputs plus the fleet makespan
    (the slowest member -- the array completes a stripe only when every
    chunk, parity included, is durable).
    """
    n_dev = len(device_traces)
    if n_dev == 0:
        return {"fleet_makespan_s": 0.0, "n": 0}
    merged = []
    for trs in device_traces:
        trs = [t for t in trs if len(t.luns)]
        if trs:
            ops, luns, chans, _ = _merge(trs, interleave)
        else:
            ops = luns = chans = np.zeros(0, dtype=np.int32)
        merged.append((ops, luns, chans))
    n_max = max(1, max(len(m[0]) for m in merged))

    def pad(a: np.ndarray) -> np.ndarray:
        out = np.zeros(n_max, dtype=np.int32)
        out[: len(a)] = a
        return out

    ops = np.stack([pad(m[0]) for m in merged])
    luns = np.stack([pad(m[1]) for m in merged])
    chans = np.stack([pad(m[2]) for m in merged])
    valid = np.stack([np.arange(n_max) < len(m[0]) for m in merged])
    t_op = jnp.asarray([flash.t_prog, flash.t_read, flash.t_erase],
                       jnp.float32)
    completions, makespans = simulate_fleet(
        jnp.asarray(ops), jnp.asarray(luns), jnp.asarray(chans),
        jnp.asarray(valid), t_op, jnp.asarray(flash.t_xfer, jnp.float32),
        flash.n_luns, flash.n_channels)
    makespans = np.asarray(makespans)
    counts = valid.sum(axis=1)
    out = {"fleet_makespan_s": float(makespans.max()),
           "n": int(counts.sum())}
    for i in range(n_dev):
        t = float(makespans[i])
        out[f"dev{i}_makespan_s"] = t
        out[f"dev{i}_n"] = int(counts[i])
        out[f"dev{i}_throughput_pages_s"] = float(counts[i] / t) if t else 0.0
    return out


def group_tagged(tagged: Sequence[Tuple[int, IOTrace]], n_devices: int
                 ) -> list:
    """Split ``(device, trace)`` pairs (as emitted by ``ZNSArray`` trace
    mode) into the per-device bundles ``run_fleet_trace`` consumes."""
    out: list = [[] for _ in range(n_devices)]
    for idx, tr in tagged:
        out[idx].append(tr)
    return out


def run_trace(flash: FlashGeometry, traces: Sequence[IOTrace],
              *, interleave: bool = True) -> dict:
    """Simulate one or more IOTraces; returns timing stats.

    ``interleave=True`` merges the traces round-robin (concurrent queues);
    ``False`` concatenates them (sequential submission).
    """
    if not traces:
        return {"makespan_s": 0.0, "n": 0, "throughput_pages_s": 0.0}
    ops, luns, chans, owner = _merge(traces, interleave)
    t_op = jnp.asarray([flash.t_prog, flash.t_read, flash.t_erase],
                       jnp.float32)
    completions, makespan = simulate(
        jnp.asarray(ops), jnp.asarray(luns), jnp.asarray(chans),
        t_op, jnp.asarray(flash.t_xfer, jnp.float32),
        flash.n_luns, flash.n_channels)
    completions = np.asarray(completions)
    makespan = float(makespan)
    out = {"makespan_s": makespan, "n": int(len(ops)),
           "throughput_pages_s": len(ops) / makespan if makespan else 0.0}
    # per-owner completion (owner 0 = first trace = usually the host)
    for i in range(len(traces)):
        sel = owner == i
        if sel.any():
            t = float(completions[sel].max())
            out[f"owner{i}_makespan_s"] = t
            out[f"owner{i}_throughput_pages_s"] = int(sel.sum()) / t if t else 0.0
    return out


def _merge(traces: Sequence[IOTrace], interleave: bool
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    ops_l, luns_l, chans_l, owner_l = [], [], [], []
    for i, tr in enumerate(traces):
        n = len(tr.luns)
        ops_l.append(np.full(n, _OP_CODE[tr.op], dtype=np.int32))
        luns_l.append(np.asarray(tr.luns, dtype=np.int32))
        chans_l.append(np.asarray(tr.channels, dtype=np.int32))
        owner_l.append(np.full(n, i, dtype=np.int32))
    if not interleave or len(traces) == 1:
        return (np.concatenate(ops_l), np.concatenate(luns_l),
                np.concatenate(chans_l), np.concatenate(owner_l))
    # round-robin merge by per-stream position (models concurrent queues)
    order_keys = np.concatenate(
        [np.arange(len(t.luns), dtype=np.int64) * len(traces) + i
         for i, t in enumerate(traces)])
    perm = np.argsort(order_keys, kind="stable")
    return (np.concatenate(ops_l)[perm], np.concatenate(luns_l)[perm],
            np.concatenate(chans_l)[perm], np.concatenate(owner_l)[perm])


def write_bandwidth_mib_s(flash: FlashGeometry, stats: dict,
                          owner: int | None = None) -> float:
    key = ("throughput_pages_s" if owner is None
           else f"owner{owner}_throughput_pages_s")
    return stats.get(key, 0.0) * flash.page_bytes / (1024 * 1024)
