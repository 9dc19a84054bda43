"""The per-op ZNS device in plain Python and numpy: the legacy oracle.

The benchmark's own copy of the simulator's pre-engine device
(``LegacyZNSDevice``), with the numpy selection it needs in place of the
jitted one, so that it imports nothing of the simulator.  It has no
silent allocator: it replays traditional lanes in full, and the
allocation-independent host pages, dummy pages and DLWA of every lane.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, Optional, Tuple

import numpy as np

from . import zns
from .elements import (ElementKind, ElementLayout, ElementSpec,
                       build_layout, elements_per_zone, groups_per_zone)
from .geometry import FlashGeometry, ZoneGeometry

#: availability codes (paper §5): 0 free, 1 allocated-empty, 2 valid
#: data, 3 invalid data (free for re-allocation after erase)
AVAIL_FREE, AVAIL_ALLOCATED, AVAIL_VALID, AVAIL_INVALID = 0, 1, 2, 3
_BIG = 2**30


class ZoneState(enum.Enum):
    EMPTY = 0
    OPEN = 1
    FULL = 2


@dataclasses.dataclass
class ZoneInfo:
    state: ZoneState = ZoneState.EMPTY
    wp: int = 0                                  # pages written (host+dummy)
    host_wp: int = 0                             # pages written by host
    elements: Optional[np.ndarray] = None        # slot -> element id (-1 = released)
    column_luns: Optional[np.ndarray] = None     # zone column -> LUN id


def select_lowest_wear(wear2d: np.ndarray, avail2d: np.ndarray,
                       eligible: np.ndarray, take: int
                       ) -> Tuple[np.ndarray, bool]:
    """Masked per-group lowest-wear selection: each eligible group gives
    its ``take`` allocatable elements of lowest (wear, index)."""
    allocatable = (avail2d == AVAIL_FREE) | (avail2d == AVAIL_INVALID)
    allocatable = allocatable & eligible[:, None]
    keyed = np.where(allocatable, wear2d, _BIG)
    order = np.argsort(keyed, axis=1, kind="stable")
    ranks = np.argsort(order, axis=1, kind="stable")
    sel = (ranks < take) & allocatable
    feasible = bool(np.all(np.where(
        eligible, allocatable.sum(axis=1) >= take, True)))
    return sel, feasible


def eligible_mask(n_groups: int, start: int, span: int) -> np.ndarray:
    """Round-robin eligible-group window (paper Eq. 6)."""
    idx = (start + np.arange(span)) % n_groups
    mask = np.zeros(n_groups, dtype=bool)
    mask[idx] = True
    return mask


class RoundRobin:
    """Rotates the eligible-group window between allocations."""

    def __init__(self, n_groups: int, span: int):
        if span > n_groups:
            raise ValueError(f"span {span} > n_groups {n_groups}")
        self.n_groups = n_groups
        self.span = span
        self._next = 0

    def next_window(self) -> np.ndarray:
        mask = eligible_mask(self.n_groups, self._next, self.span)
        self._next = (self._next + self.span) % self.n_groups
        return mask


class LegacyZNSDevice:
    """One emulated ZNS SSD, stateful-Python edition (pre-engine)."""

    def __init__(self,
                 flash: FlashGeometry,
                 zone_geom: ZoneGeometry,
                 spec: ElementSpec,
                 *,
                 max_active: int = 14,
                 wear_aware: Optional[bool] = None):
        self.flash = flash
        self.zone_geom = zone_geom
        self.spec = spec
        self.max_active = max_active
        # the ConfZNS++ fixed baseline ignores wear (paper §6.2)
        self.wear_aware = (spec.kind is not ElementKind.FIXED
                           if wear_aware is None else wear_aware)

        self.layout: ElementLayout = build_layout(flash, spec, zone_geom)
        self.elems_per_zone = elements_per_zone(self.layout, zone_geom)
        self.zone_groups = groups_per_zone(self.layout, zone_geom)
        self.take_per_group = self.elems_per_zone // self.zone_groups
        self.zone_pages = zone_geom.zone_pages(flash)
        self.n_zones = flash.n_blocks // zone_geom.blocks_per_zone

        n = self.layout.n_elements
        self.per_group = n // self.layout.n_groups
        self.elem_wear = np.zeros(n, dtype=np.int64)
        self.elem_avail = np.full(n, AVAIL_FREE, dtype=np.int32)
        self.elem_pages = np.zeros(n, dtype=np.int64)
        self.elem_zone = np.full(n, -1, dtype=np.int32)
        self.zones: Dict[int, ZoneInfo] = {z: ZoneInfo() for z in range(self.n_zones)}
        self.rr = RoundRobin(self.layout.n_groups, self.zone_groups)

        # counters
        self.host_pages = 0
        self.dummy_pages = 0
        self.block_erases = 0

    # ------------------------------------------------------------------ #
    # metrics
    # ------------------------------------------------------------------ #
    @property
    def dlwa(self) -> float:
        if self.host_pages == 0:
            return 1.0
        return (self.host_pages + self.dummy_pages) / self.host_pages

    @property
    def n_active(self) -> int:
        return sum(1 for z in self.zones.values() if z.state is ZoneState.OPEN)

    # ------------------------------------------------------------------ #
    # allocation (paper §5)
    # ------------------------------------------------------------------ #
    def _wear2d(self) -> np.ndarray:
        return self.elem_wear.reshape(self.layout.n_groups, self.per_group)

    def _avail2d(self) -> np.ndarray:
        return self.elem_avail.reshape(self.layout.n_groups, self.per_group)

    def _allocate_zone(self, zone_id: int) -> None:
        info = self.zones[zone_id]
        if self.n_active >= self.max_active:
            raise RuntimeError(
                f"open/active zone limit ({self.max_active}) reached")

        if self.spec.kind is ElementKind.FIXED:
            sel_ids = self._allocate_fixed()  # shape (1,): one static zone
            window_groups = np.asarray(
                [self.layout.group[int(sel_ids[0])]], dtype=np.int64)
        else:
            eligible = self.rr.next_window()
            if self.wear_aware:
                sel, feasible = select_lowest_wear(
                    self._wear2d(), self._avail2d(), eligible,
                    self.take_per_group)
            else:
                sel, feasible = self._first_available(eligible)
            if not feasible:
                # round-robin window exhausted: activate the cheapest
                # feasible groups instead (ILP with L_min = zone_groups --
                # optimal group choice = smallest sum of take-lowest wears)
                eligible = self._cheapest_groups()
                sel, feasible = select_lowest_wear(
                    self._wear2d(), self._avail2d(), eligible,
                    self.take_per_group)
            if not feasible:
                raise RuntimeError("no free storage elements for zone "
                                   f"{zone_id} ({self.spec.name})")
            sel2d = sel.reshape(self.layout.n_groups, self.per_group)
            window_groups = np.nonzero(sel2d.any(axis=1))[0]
            sel_ids = self._arrange(sel2d, window_groups)

        flat = sel_ids.reshape(-1)
        # deferred physical erase of invalid elements (paper §5 RESET)
        invalid = flat[self.elem_avail[flat] == AVAIL_INVALID]
        if invalid.size:
            self.elem_wear[invalid] += 1
            self.block_erases += invalid.size * self.layout.blocks_per_element
        self.elem_avail[flat] = AVAIL_ALLOCATED
        self.elem_pages[flat] = 0
        self.elem_zone[flat] = zone_id

        info.elements = sel_ids
        info.column_luns = self._column_luns(window_groups)
        info.state = ZoneState.OPEN
        info.wp = 0
        info.host_wp = 0

    def _cheapest_groups(self) -> np.ndarray:
        """Pick the ``zone_groups`` groups minimizing the sum of their
        ``take`` lowest available wears (exact for the balanced ILP)."""
        wear2d = self._wear2d().astype(np.float64)
        avail2d = self._avail2d()
        ok = (avail2d == AVAIL_FREE) | (avail2d == AVAIL_INVALID)
        keyed = np.where(ok, wear2d, np.inf)
        part = np.sort(keyed, axis=1)[:, : self.take_per_group]
        cost = part.sum(axis=1)  # inf when < take available
        order = np.argsort(cost, kind="stable")[: self.zone_groups]
        mask = np.zeros(self.layout.n_groups, dtype=bool)
        mask[order] = True
        return mask

    def _first_available(self, eligible: np.ndarray
                         ) -> Tuple[np.ndarray, bool]:
        """Wear-oblivious first-fit (baseline allocation policy)."""
        avail2d = self._avail2d()
        ok = ((avail2d == AVAIL_FREE) | (avail2d == AVAIL_INVALID))
        ok &= eligible[:, None]
        idx = np.argsort(~ok, axis=1, kind="stable")  # available first
        ranks = np.argsort(idx, axis=1, kind="stable")
        sel = ok & (ranks < self.take_per_group)
        feasible = bool(np.all(np.where(
            eligible, ok.sum(axis=1) >= self.take_per_group, True)))
        return sel, feasible

    def _allocate_fixed(self) -> np.ndarray:
        ok = np.isin(self.elem_avail, (AVAIL_FREE, AVAIL_INVALID))
        ids = np.nonzero(ok)[0]
        if not ids.size:
            raise RuntimeError("no free physical zone (fixed mapping)")
        if self.wear_aware:
            e = ids[np.argmin(self.elem_wear[ids])]
        else:
            e = ids[0]
        return np.asarray([e], dtype=np.int64)

    def _arrange(self, sel2d: np.ndarray, window_groups: np.ndarray
                 ) -> np.ndarray:
        """Order selected elements into zone slots (see zns.py ordering).

        Returns (n_slots,) element ids; within each group, selected
        elements are ranked by wear and assigned to segments bottom-up.
        """
        n_slots = zns.n_slots(self.spec, self.zone_geom.parallelism,
                              self.zone_geom.n_segments)
        out = np.full(n_slots, -1, dtype=np.int64)
        for c, g in enumerate(window_groups):
            cols = np.nonzero(sel2d[g])[0]
            ids = g * self.per_group + cols
            order = np.argsort(self.elem_wear[ids], kind="stable")
            for rank, eid in enumerate(ids[order]):
                slot = zns.slot_of_group_rank(
                    self.spec, self.zone_geom.parallelism,
                    self.zone_geom.n_segments, c, rank)
                out[slot] = eid
        assert (out >= 0).all(), "zone slot assignment incomplete"
        return out

    def _column_luns(self, window_groups: np.ndarray) -> np.ndarray:
        """Zone column -> LUN id, from the groups that won the allocation.

        FIXED-zone column convention: a static physical zone is pinned to
        ``parallelism`` *adjacent* LUNs starting at ``group * parallelism``
        (its erase blocks are laid out contiguously, so the winning group
        index alone determines every column).  Dynamic elements instead
        contribute ``luns_per_group`` columns per winning group.
        """
        s = self.layout.luns_per_group
        luns = []
        for g in window_groups:
            if self.spec.kind is ElementKind.FIXED:
                base = int(g) * self.zone_geom.parallelism
                luns.extend(range(base, base + self.zone_geom.parallelism))
            else:
                luns.extend(range(int(g) * s, int(g) * s + s))
        return np.asarray(luns[: self.zone_geom.parallelism], dtype=np.int64)

    # ------------------------------------------------------------------ #
    # ZNS commands
    # ------------------------------------------------------------------ #
    def zone_write(self, zone_id: int, n_pages: int, *,
                   host: bool = True) -> None:
        info = self.zones[zone_id]
        if info.state is ZoneState.FULL:
            raise RuntimeError(f"write to FULL zone {zone_id}")
        if info.state is ZoneState.EMPTY:
            self._allocate_zone(zone_id)
        if info.wp + n_pages > self.zone_pages:
            raise RuntimeError(
                f"zone {zone_id} overflow: wp={info.wp} + {n_pages} "
                f"> {self.zone_pages}")
        info.wp += n_pages
        if host:
            info.host_wp += n_pages
            self.host_pages += n_pages
        else:
            self.dummy_pages += n_pages
        self._refresh_element_pages(info)
        if info.wp == self.zone_pages:
            self._seal(info)

    def zone_read(self, zone_id: int) -> None:
        if self.zones[zone_id].column_luns is None:
            raise RuntimeError(f"read from unmapped zone {zone_id}")

    def zone_finish(self, zone_id: int) -> None:
        """FINISH: pad partially-written elements, release untouched ones."""
        info = self.zones[zone_id]
        if info.state is ZoneState.FULL:
            return
        if info.state is ZoneState.EMPTY:
            info.state = ZoneState.FULL  # finishing an empty zone is a no-op
            return
        written = zns.element_pages(
            info.wp, self.spec, self.zone_geom.parallelism,
            self.zone_geom.n_segments, self.flash.pages_per_block)
        cap = self.layout.pages_per_element
        elems = info.elements
        for slot, eid in enumerate(elems):
            if eid < 0:
                continue
            w = int(written[slot])
            if w == 0:
                # untouched: release back to the pool (a=1 -> a=0)
                self.elem_avail[eid] = AVAIL_FREE
                self.elem_zone[eid] = -1
                self.elem_pages[eid] = 0
                info.elements[slot] = -1
            else:
                self.dummy_pages += cap - w
                self.elem_pages[eid] = cap
                self.elem_avail[eid] = AVAIL_VALID
        self._seal(info)

    def zone_reset(self, zone_id: int) -> None:
        """Partial + asynchronous RESET (paper §5): invalidate metadata,
        defer physical erase to re-allocation."""
        info = self.zones[zone_id]
        if info.elements is not None:
            for eid in info.elements:
                if eid < 0:
                    continue
                if self.elem_avail[eid] == AVAIL_VALID:
                    self.elem_avail[eid] = AVAIL_INVALID
                elif self.elem_avail[eid] == AVAIL_ALLOCATED:
                    self.elem_avail[eid] = AVAIL_FREE
                self.elem_zone[eid] = -1
                self.elem_pages[eid] = 0
        self.zones[zone_id] = ZoneInfo()

    # ------------------------------------------------------------------ #
    # helpers
    # ------------------------------------------------------------------ #
    def _seal(self, info: ZoneInfo) -> None:
        info.state = ZoneState.FULL

    def _refresh_element_pages(self, info: ZoneInfo) -> None:
        written = zns.element_pages(
            info.wp, self.spec, self.zone_geom.parallelism,
            self.zone_geom.n_segments, self.flash.pages_per_block)
        elems = info.elements
        valid = elems >= 0
        self.elem_pages[elems[valid]] = written[valid]
        # first host byte into an element transitions it a=1 -> a=2? The
        # paper marks written elements valid at WRITE time (§5 READ/WRITE).
        touched = valid & (written > 0)
        self.elem_avail[elems[touched]] = AVAIL_VALID


def replay(flash: FlashGeometry, zone_geom: ZoneGeometry, spec: ElementSpec,
           wear_aware: bool, max_active: int,
           program: np.ndarray) -> LegacyZNSDevice:
    """Replay one lane's op rows through the per-op device (op codes:
    1 ALLOC, 2 WRITE, 3 FINISH, 4 RESET, 5 READ; flags bit 0 = host)."""
    leg = LegacyZNSDevice(flash, zone_geom, spec, max_active=max_active,
                          wear_aware=wear_aware)
    for op, zone, n, flags in np.asarray(program)[:, :4].tolist():
        if op == 1:
            # an ALLOC row maps an EMPTY zone where it stands (a striped
            # lane allocates before its first chunk arrives), else no-op
            if leg.zones[zone].state is ZoneState.EMPTY:
                leg._allocate_zone(zone)
        elif op == 2:
            leg.zone_write(zone, n, host=bool(flags & 1))
        elif op == 3:
            leg.zone_finish(zone)
        elif op == 4:
            leg.zone_reset(zone)
        elif op == 5:
            leg.zone_read(zone)
    return leg
