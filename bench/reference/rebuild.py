"""A member failure and its rebuild under load, in plain Python.

The benchmark's own statement of what the fleet builder compiles when
one member of a log-structured RAID-5 array (parity on) fails before
logical row ``int(at * n_rows)`` of the merged tenant stream:

* the rows before the failure stripe as usual; the failed member's are
  dropped with it (the replacement is a fresh device);
* at the failure, for every superzone with data, every chunk row the
  member held is read by each survivor that wrote that row and
  appended, reconstructed, to the replacement; a FULL superzone is
  FINISHed there once it got data.  These rows carry the tag
  ``parity_tenant + 1``;
* the rest of the stream stripes as usual, and on each member lane its
  rows and the rebuild rows interleave round robin, foreground first,
  except that a foreground RESET of a zone (on the replacement, any
  foreground row of a zone) waits until that lane's rebuild rows of
  the zone are out.

It takes the striped lanes of :mod:`.stripe` and adds the timing of the
rebuild as one more closed-loop stream per lane, and the rollups a
user reads after a failure.  The failure happens at one instant for
the whole array, the latest completion of a row issued before it: no
later row of the array is issued before that instant, and a chunk
appended to the replacement is issued once every survivor read it is
computed from has completed.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence

import numpy as np

from . import clock, stripe
from .model import F_HOST, OP_FINISH, OP_READ, OP_RESET, OP_WRITE, replay_lane
from .static import real_cells


class Failed(NamedTuple):
    lanes: List[np.ndarray]   # per member, width 5
    marks: List[int]          # per member: rows before the failure
    waits: List[tuple]        # (member, row, member, row): the first
                              #   row is issued once the second is done


def _live(rows: np.ndarray, cap: int) -> Dict[int, Dict]:
    """Superzone -> logical write pointer and whether it is FULL."""
    zones: Dict[int, Dict] = {}
    for op, zone, n_pages, _, _ in np.asarray(rows).tolist():
        z = zones.setdefault(zone, {"wp": 0, "full": False})
        if op == OP_WRITE:
            z["wp"] += n_pages
            z["full"] = z["full"] or z["wp"] == cap
        elif op == OP_FINISH:
            z["full"] = True
        elif op == OP_RESET:
            zones[zone] = {"wp": 0, "full": False}
    return zones


def _held(zone: int, stripe_no: int, member: int, *, wp: int, full: bool,
          n_devices: int, c: int) -> int:
    """Pages ``member`` wrote for chunk row ``stripe_no`` of ``zone``."""
    n_data = n_devices - 1
    p = stripe.parity_member(zone, stripe_no, n_devices)
    if p == member:
        # a stripe's parity lands once it completes, or at FINISH
        done = wp // (c * n_data) + (1 if full and wp % (c * n_data) else 0)
        return c if stripe_no < done else 0
    slot = member if member < p else member - 1
    start = stripe_no * c * n_data + slot * c
    return max(0, min(c, wp - start))


def plan(live: Dict[int, Dict], member: int, *, n_devices: int, c: int,
         stripes: int, tag: int) -> tuple:
    """The rebuild's rows per member lane, and for each chunk appended
    to the replacement the reads it is computed from: ``(k, d, j)``,
    the replacement's k-th rebuild row reads member d's j-th."""
    out: List[List[tuple]] = [[] for _ in range(n_devices)]
    sources: List[tuple] = []
    for zone in sorted(live):
        wp, full = live[zone]["wp"], live[zone]["full"]
        if wp == 0:
            continue
        held = [[_held(zone, s, d, wp=wp, full=full, n_devices=n_devices,
                       c=c) for s in range(stripes)]
                for d in range(n_devices)]
        wrote = 0
        for s in range(stripes):
            n = held[member][s]
            if n == 0:
                continue
            for d in range(n_devices):
                written = sum(held[d])
                if d != member and written > s * c:
                    sources.append((len(out[member]), d, len(out[d])))
                    out[d].append((OP_READ, zone, min(n, written - s * c),
                                   0, tag))
            out[member].append((OP_WRITE, zone, n, F_HOST, tag))
            wrote += n
        if full and wrote:
            out[member].append((OP_FINISH, zone, 0, 0, tag))
    return out, sources


def merge(foreground: Sequence[tuple], rebuild: Sequence[tuple],
          replacement: bool) -> List[tuple]:
    """Round robin, foreground first, with the reset/replacement hold."""
    out: List[tuple] = []
    queue = list(rebuild)
    for row in foreground:
        zone = row[1]
        if replacement or row[0] == OP_RESET:
            while any(r[1] == zone for r in queue):
                out.append(queue.pop(0))
        out.append(row)
        if queue:
            out.append(queue.pop(0))
    return out + queue


def fail(merged: np.ndarray, striped: Sequence[np.ndarray], *, member: int,
         at: float, n_devices: int, chunk_pages: int,
         member_zone_pages: int, parity_tenant: int) -> Failed:
    """The lanes of one array whose ``member`` fails at share ``at`` of
    the merged rows; ``striped`` are the healthy array's lanes."""
    f = int(at * len(merged))
    before = stripe.stripe(merged[:f], n_devices=n_devices,
                           chunk_pages=chunk_pages, parity=True,
                           member_zone_pages=member_zone_pages,
                           parity_tenant=parity_tenant)
    marks = [len(b) for b in before]
    cap = (n_devices - 1) * member_zone_pages
    tag = parity_tenant + 1
    rebuild, sources = plan(_live(merged[:f], cap), member,
                            n_devices=n_devices, c=chunk_pages,
                            stripes=member_zone_pages // chunk_pages,
                            tag=tag)
    lanes = []
    for d in range(n_devices):
        rows = [tuple(r) for r in np.asarray(striped[d]).tolist()]
        keep = [] if d == member else rows[: marks[d]]
        lanes.append(np.asarray(
            keep + merge(rows[marks[d]:], rebuild[d], d == member),
            dtype=np.int32).reshape(-1, 5))
    marks[member] = 0
    # the merge keeps a lane's rebuild rows in order, and only they
    # carry the tag
    at = [np.flatnonzero(lane[:, 4] == tag) for lane in lanes]
    waits = [(member, int(at[member][k]), d, int(at[d][j]))
             for k, d, j in sources]
    return Failed(lanes, marks, waits)


def busy_clock(cols, pages, tenants, t_page, n_luns: int, n_tenants: int,
               ready, dtype=np.float32):
    """:func:`.clock.busy_clock` with an earliest issue time per op:
    an op is issued when its tenant's previous op is done and not
    before ``ready``, and its latency runs from its issue."""
    n_lanes, n_ops, p = cols.shape
    rows = np.arange(n_lanes)
    lun_free = np.zeros((n_lanes, n_luns), dtype)
    ten_done = np.zeros((n_lanes, n_tenants), dtype)
    done = np.zeros((n_lanes, n_ops), dtype)
    lat = np.zeros((n_lanes, n_ops), dtype)
    t_page = np.asarray(t_page).astype(dtype)
    ready = np.asarray(ready).astype(dtype)
    for i in range(n_ops):
        act = pages[:, i] > 0
        c, t = cols[:, i], tenants[:, i]
        dur = ((pages[:, i] + p - 1) // p).astype(dtype) * t_page[:, i]
        issued = np.maximum(ten_done[rows, t], ready[:, i])
        d = (np.maximum(lun_free[rows[:, None], c].max(axis=1), issued)
             + dur).astype(dtype)
        done[act, i] = d[act]
        lat[act, i] = (d - issued)[act]
        lun_free[rows[act, None], c[act]] = d[act, None]
        ten_done[rows[act], t[act]] = d[act]
    return done, lat, lun_free.max(axis=1)


def failure_clock(cols, pages, tenants, t_page, n_luns: int,
                  n_tenants: int, failures: Sequence[tuple],
                  dtype=np.float32):
    """The clock of a batch whose arrays ``failures`` (``(first lane,
    Failed)`` each) lose a member: first the rows before each failure,
    which give its instant; then every later row held to it; then each
    rebuilt chunk held to its survivor reads as the second run timed
    them."""
    n_lanes, n_ops = pages.shape
    ready = np.zeros((n_lanes, n_ops), dtype)
    done = busy_clock(cols, pages, tenants, t_page, n_luns, n_tenants,
                      ready, dtype)[0]
    for base, f in failures:
        lanes = range(base, base + len(f.marks))
        instant = max((done[j, :m].max(initial=0)
                       for j, m in zip(lanes, f.marks)), default=0)
        for j, m in zip(lanes, f.marks):
            ready[j, m:] = instant
    done = busy_clock(cols, pages, tenants, t_page, n_luns, n_tenants,
                      ready, dtype)[0]
    for base, f in failures:
        for d, row, src, src_row in f.waits:
            if row < n_ops and src_row < n_ops:
                ready[base + d, row] = max(ready[base + d, row],
                                           done[base + src, src_row])
    return busy_clock(cols, pages, tenants, t_page, n_luns, n_tenants,
                      ready, dtype)


def run_reference(static, flash, programs: np.ndarray,
                  values: Sequence[Dict], parity_tenant: int,
                  failures: Sequence[tuple], dtype=np.float32) -> Dict:
    """:func:`.check.run_reference` with the rebuild's tag on the clock
    (tags up to ``parity_tenant + 1``) and the failures' order on it
    (:func:`failure_clock`; ``failures`` as it takes them)."""
    lanes = [replay_lane(static, v, p) for v, p in zip(values, programs)]
    out = {k: np.stack([r[k] for r in lanes])
           for k in ("ok", "host_delta", "dummy_delta", "erase_delta",
                     "pages", "cols")}
    out["states"] = [r["state"] for r in lanes]
    out["t_page"] = clock.page_times(programs, flash)
    out["n_luns"] = flash.n_luns
    done, lat, span = failure_clock(
        out["cols"], out["pages"], programs[:, :, 4], out["t_page"],
        flash.n_luns, parity_tenant + 2, failures, dtype=dtype)
    out["completions"] = done.astype(np.float32)
    out["latencies"] = lat.astype(np.float32)
    out["makespans"] = span.astype(np.float32)
    out["programs"] = np.asarray(programs)
    out["mask"] = np.stack([real_cells(static, v) for v in values])
    out["parity_tenant"] = parity_tenant
    return out


def recovery_row(ref: Dict, idx, marks: Sequence[int],
                 n_tenants: int) -> Dict[str, float]:
    """What a failed and rebuilt array's row adds: time to recover,
    rebuilt pages, each tenant's p99 after the failure, and each
    member's DLWA."""
    idx = np.asarray(idx)
    tag = ref["parity_tenant"] + 1
    t = ref["programs"][idx][:, :, 4]
    done = ref["completions"][idx]
    lat = ref["latencies"][idx]
    after = np.arange(done.shape[1])[None, :] >= np.asarray(marks)[:, None]
    rb = t == tag
    row = {"recover_s": 0.0,
           "rebuild_pages": float(int(ref["host_delta"][idx][rb].sum()))}
    if rb.any():
        last_before = (done[~after].max() if (~after).any()
                       else np.float32(0))
        gap = done[rb].max() - last_before
        row["recover_s"] = float(max(gap, np.float32(0)))
    act = after & (ref["pages"][idx] > 0)
    for k in range(n_tenants):
        sel = act & (t == k)
        row[f"tenant{k}_p99_after_failure_s"] = (
            float(np.percentile(lat[sel], 99)) if sel.any() else 0.0)
    for d, j in enumerate(idx):
        s = ref["states"][j]
        h, pad = int(s["host_pages"]), int(s["dummy_pages"])
        row[f"member{d}_dlwa"] = (h + pad) / h if h else 1.0
    return row
