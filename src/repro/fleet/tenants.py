"""Tenant-tagged op programs: encode *who* issued each zone command.

The engine's op rows are ``[opcode, zone, n_pages, flags]`` (see
:mod:`repro.core.engine`); this module appends an engine-opaque fifth
column -- the **tenant tag** -- and provides the transforms that turn
per-tenant workload programs into one executable program per device:

* :func:`tag_tenant`          -- widen a width-4 program to width 5 and
                                 stamp a tenant id on every row;
* :func:`interleave_tenants`  -- merge per-tenant programs round-robin
                                 by per-tenant position, the same
                                 concurrent-submission-queue model the
                                 timing layer uses for IO streams;
* :func:`stripe_program`      -- rewrite a *logical* (superzone-
                                 addressed) program into per-member
                                 *physical* programs at zone-chunk
                                 granularity, with optional RAID-5-style
                                 log-structured parity appends, using
                                 the exact stripe math of
                                 :class:`repro.array.ZNSArray`;
* :func:`stripe_rebuild`      -- the same with one member failing at a
                                 row and rebuilt under the rest of the
                                 stream (rebuild rows tagged
                                 ``parity_tenant + 1``);
* :func:`pad_programs`        -- right-pad ragged per-device programs
                                 with NOP rows so a fleet stacks into
                                 the rectangular batch ``run_programs``
                                 consumes.

Units: ``n_pages`` counts flash pages; zones/tenants/devices are dense
int indexes.  Parity rows carry the reserved tag passed as
``parity_tenant`` (by convention ``n_tenants``, one past the real
tenants) so array-level DLWA can separate parity from host data.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Set

import numpy as np

from repro.array.engine import merge_rebuild, plan_rebuild
from repro.array.raid import SuperZoneInfo, locate_page, parity_device_of
from repro.core import engine as zengine
from repro.core.device import ZoneState
from repro.obs.profile import count, span

#: column index of the tenant tag in a width-5 op row
TENANT_COL = 4


def tag_tenant(program: np.ndarray, tenant: int) -> np.ndarray:
    """Widen ``(n_ops, >=4)`` to width 5 and stamp ``tenant`` on every
    row (an already-width-5 program is re-stamped)."""
    program = np.asarray(program, dtype=np.int32)
    out = np.zeros((len(program), TENANT_COL + 1), dtype=np.int32)
    out[:, :4] = program[:, :4]
    out[:, TENANT_COL] = tenant
    return out


def interleave_tenants(programs: Sequence[np.ndarray]) -> np.ndarray:
    """Merge tenant programs round-robin by per-tenant op position.

    Models concurrent per-tenant submission queues drained fairly --
    exactly the merge :func:`repro.core.timing._merge` applies to IO
    streams, lifted to op granularity.  A single program passes through
    unchanged (so 1 tenant x 1 device is bit-identical to the plain
    ``run_program`` path -- tested).
    """
    programs = [np.asarray(p, dtype=np.int32) for p in programs if len(p)]
    if not programs:
        return np.zeros((0, TENANT_COL + 1), dtype=np.int32)
    width = max(p.shape[1] for p in programs)
    programs = [p if p.shape[1] == width else
                np.pad(p, ((0, 0), (0, width - p.shape[1])))
                for p in programs]
    if len(programs) == 1:
        return programs[0]
    order_keys = np.concatenate(
        [np.arange(len(p), dtype=np.int64) * len(programs) + i
         for i, p in enumerate(programs)])
    perm = np.argsort(order_keys, kind="stable")
    return np.concatenate(programs)[perm]


def stripe_program(program: np.ndarray, *, n_devices: int,
                   chunk_pages: int, parity: bool,
                   member_zone_pages: int, parity_tenant: int
                   ) -> List[np.ndarray]:
    """Rewrite a logical superzone program into per-member programs.

    The logical address space is :class:`repro.array.ZNSArray`'s: a
    superzone ``z`` maps to physical zone ``z`` on every member, host
    pages stripe at ``chunk_pages`` granularity across the ``n_data``
    data members of each stripe, and (with ``parity``) one parity chunk
    per stripe is appended to the rotating parity member as soon as the
    stripe completes -- or, for the final partial stripe, at FINISH.
    FINISH/RESET fan out to every member.  Each member's program is a
    strictly sequential append stream per zone, which is what a ZNS
    zone requires and what keeps SilentZNS allocation valid underneath.

    ``member_zone_pages`` is the *effective* member zone capacity in
    pages (a ``DynConfig`` override under heterogeneous geometries);
    the logical superzone capacity is ``n_data * member_zone_pages``.
    Parity rows are tagged ``parity_tenant``.

    Returns ``n_devices`` programs of width 5 (ragged lengths -- see
    :func:`pad_programs`).
    """
    striper = _Striper(n_devices=n_devices, chunk_pages=chunk_pages,
                       parity=parity, member_zone_pages=member_zone_pages,
                       parity_tenant=parity_tenant)
    striper.feed(program)
    return [zengine.encode_program(rows, width=TENANT_COL + 1)
            for rows in striper.out]


class StripedRebuild(NamedTuple):
    """What :func:`stripe_rebuild` compiles for one array."""

    lanes: List[np.ndarray]  # per member, width 5 (ragged)
    marks: List[int]         # per member: rows issued before the failure
    waits: np.ndarray        # (K, 3) int: a replacement row, a survivor
                             #   and its row the replacement's waits for


def stripe_rebuild(program: np.ndarray, *, n_devices: int,
                   chunk_pages: int, parity: bool,
                   member_zone_pages: int, parity_tenant: int,
                   member: int, at_row: int) -> StripedRebuild:
    """:func:`stripe_program` with member ``member`` failing before
    logical row ``at_row`` and rebuilt while the rest runs.

    The rows before the failure stripe as usual.  At the failure the
    rebuild is compiled from the superzones' state by
    :func:`repro.array.plan_rebuild` (the plan ``ArrayEngine.
    rebuild_device`` runs): survivor READs of every chunk row the
    member held and the reconstructed appends, tagged ``parity_tenant
    + 1``.  The remaining rows stripe as usual and, on each member
    lane, :func:`repro.array.merge_rebuild` interleaves them with the
    rebuild round robin, holding a foreground row back while its
    zone's rebuild is queued (a RESET of the zone; on the replacement
    any row of it).  The failed member's rows before the failure are
    dropped: the replacement is a fresh device.  ``waits`` pairs each
    chunk appended to the replacement with the survivor reads it is
    computed from, for the timing.  Under a current profiler the plan
    and the merge are timed as ``build.rebuild`` and counted
    (``build.rebuild_rows``, ``build.rebuild_read_pages``,
    ``build.rebuild_held_rows``).
    """
    if not parity:
        raise ValueError("a rebuild needs parity: with parity off the "
                         "failed member's data is lost")
    if not 0 <= member < n_devices:
        raise ValueError(f"failed member {member} is not one of the "
                         f"{n_devices} members")
    program = np.asarray(program, dtype=np.int32)
    at_row = min(max(at_row, 0), len(program))
    striper = _Striper(n_devices=n_devices, chunk_pages=chunk_pages,
                       parity=parity, member_zone_pages=member_zone_pages,
                       parity_tenant=parity_tenant)
    striper.feed(program[:at_row])
    marks = [len(rows) for rows in striper.out]
    marks[member] = 0
    with span("build.rebuild"):
        tag = parity_tenant + 1
        rebuild: List[List[tuple]] = [[] for _ in range(n_devices)]
        # (k-th rebuild row of the replacement, survivor, j-th of its)
        waits: List[tuple] = []
        reads: List[tuple] = []
        read_pages = 0
        for d, op, z, _, n in plan_rebuild(
                striper.live(), member, chunk_pages=chunk_pages,
                n_devices=n_devices,
                stripes_per_zone=member_zone_pages // chunk_pages):
            flags = zengine.F_HOST if op == zengine.OP_WRITE else 0
            if op == zengine.OP_READ:
                read_pages += n
                reads.append((d, len(rebuild[d])))
            elif op == zengine.OP_WRITE:
                waits += [(len(rebuild[d]), *r) for r in reads]
                reads = []
            rebuild[d].append((op, z, n, flags, tag))
    tail_from = [len(rows) for rows in striper.out]
    striper.feed(program[at_row:])
    with span("build.rebuild"):
        lanes, held = [], 0
        for d, rows in enumerate(striper.out):
            merged, h = merge_rebuild(rows[tail_from[d]:], rebuild[d],
                                      replacement=d == member)
            held += h
            lanes.append(zengine.encode_program(
                rows[:marks[d]] + merged, width=TENANT_COL + 1))
        # the merge keeps each lane's rebuild rows in order: the k-th
        # rebuild row of a lane is its k-th row carrying the tag
        at = [np.flatnonzero(lane[:, TENANT_COL] == tag) for lane in lanes]
        waits_rows = np.asarray(
            [(at[member][k], d, at[d][j]) for k, d, j in waits],
            dtype=np.int64).reshape(-1, 3)
        count("build.rebuild_rows", sum(len(r) for r in rebuild))
        count("build.rebuild_read_pages", read_pages)
        count("build.rebuild_held_rows", held)
    return StripedRebuild(lanes, marks, waits_rows)


class _Striper:
    """The RAID-5 striping state machine behind :func:`stripe_program`:
    per-member row lists and, per superzone, the logical write pointer,
    the parity stripes emitted and whether it is FULL."""

    def __init__(self, *, n_devices: int, chunk_pages: int, parity: bool,
                 member_zone_pages: int, parity_tenant: int):
        if n_devices < 1:
            raise ValueError("n_devices must be >= 1")
        if parity and n_devices < 2:
            raise ValueError("parity needs >= 2 devices")
        if member_zone_pages % chunk_pages:
            raise ValueError(
                f"chunk_pages={chunk_pages} must divide the member "
                f"zone capacity ({member_zone_pages} pages)")
        self.n_devices, self.parity = n_devices, parity
        self.parity_tenant = parity_tenant
        self.n_data = n_devices - (1 if parity else 0)
        self.cap = self.n_data * member_zone_pages
        self.c = chunk_pages
        self.out: List[List[tuple]] = [[] for _ in range(n_devices)]
        self.wp: Dict[int, int] = {}        # superzone -> logical wp
        self.emitted: Dict[int, int] = {}   # superzone -> parity stripes
        self.full: Set[int] = set()         # FULL superzones

    def live(self) -> Dict[int, SuperZoneInfo]:
        """The superzones' metadata, as the array keeps it."""
        return {z: SuperZoneInfo(
            state=ZoneState.FULL if z in self.full else ZoneState.OPEN,
            wp=self.wp.get(z, 0), parity_emitted=self.emitted.get(z, 0))
            for z in set(self.wp) | set(self.emitted)}

    def feed(self, program: np.ndarray) -> None:
        """Stripe logical rows onto the member lists."""
        n_devices, parity, cap, c = (self.n_devices, self.parity,
                                     self.cap, self.c)
        n_data, parity_tenant = self.n_data, self.parity_tenant
        out, wp, emitted, full = self.out, self.wp, self.emitted, self.full

        def emit_parity(zone: int, upto_stripe: int) -> None:
            if not parity:
                return
            while emitted.get(zone, 0) < upto_stripe:
                s = emitted.get(zone, 0)
                p = parity_device_of(zone, s, n_devices)
                out[p].append((zengine.OP_WRITE, zone, c, zengine.F_HOST,
                               parity_tenant))
                emitted[zone] = s + 1

        program = np.asarray(program, dtype=np.int32)
        for row in program:
            op, zone, n_pages = int(row[0]), int(row[1]), int(row[2])
            flags = int(row[3])
            tenant = int(row[TENANT_COL]) if len(row) > TENANT_COL else 0
            if op == zengine.OP_WRITE:
                page = wp.get(zone, 0)
                if page + n_pages > cap:
                    raise ValueError(
                        f"superzone {zone} overflow: wp={page} + "
                        f"{n_pages} > {cap}")
                remaining = n_pages
                while remaining > 0:
                    stripe, _, r, dev = locate_page(
                        zone, page, c, n_data, n_devices, parity)
                    # parity of every completed stripe lands before this
                    # member appends its next chunk (log-structured order)
                    emit_parity(zone, stripe)
                    take = min(c - r, remaining)
                    out[dev].append((op, zone, take, flags, tenant))
                    page += take
                    remaining -= take
                wp[zone] = page
                emit_parity(zone, page // (c * n_data))
                if page == cap:
                    full.add(zone)
            elif op == zengine.OP_FINISH:
                page = wp.get(zone, 0)
                full_stripes = page // (c * n_data)
                emit_parity(zone, full_stripes)
                # partial-stripe parity exactly once (a repeated FINISH
                # is a no-op, matching ZNSArray's FULL-zone semantics)
                if (parity and page % (c * n_data)
                        and emitted.get(zone, 0) <= full_stripes):
                    # parity over the final partial stripe covers the
                    # written prefix (unwritten data reads as zeros)
                    p = parity_device_of(zone, full_stripes, n_devices)
                    out[p].append((zengine.OP_WRITE, zone, c,
                                   zengine.F_HOST, parity_tenant))
                    emitted[zone] = full_stripes + 1
                for dev in range(n_devices):
                    out[dev].append((op, zone, 0, 0, tenant))
                full.add(zone)
            elif op == zengine.OP_RESET:
                for dev in range(n_devices):
                    out[dev].append((op, zone, 0, 0, tenant))
                wp.pop(zone, None)
                emitted.pop(zone, None)
                full.discard(zone)
            else:  # NOP/ALLOC/READ: replicate (state-neutral or per-member)
                for dev in range(n_devices):
                    out[dev].append((op, zone, n_pages, flags, tenant))


def pad_programs(programs: Sequence[np.ndarray],
                 n_ops: int | None = None) -> np.ndarray:
    """Right-pad ragged programs with NOP rows and stack to
    ``(n_programs, n_ops, 5)`` -- the rectangular batch
    ``run_programs`` consumes.  NOP rows are all-zero (``OP_NOP``
    moves no pages and touches no state)."""
    programs = [np.asarray(p, dtype=np.int32) for p in programs]
    width = max((p.shape[1] for p in programs if p.ndim == 2),
                default=TENANT_COL + 1)
    n_max = n_ops if n_ops is not None else max(
        (len(p) for p in programs), default=0)
    out = np.zeros((len(programs), n_max, width), dtype=np.int32)
    for i, p in enumerate(programs):
        if len(p) > n_max:
            raise ValueError(f"program {i} has {len(p)} ops > {n_max}")
        out[i, : len(p), : p.shape[1]] = p
    return out
