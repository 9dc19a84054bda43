"""The device state machine in plain numpy: the engine's reference.

The benchmark's own copy of the simulator's op-program verifier model
(a numpy mirror of the engine's transitions, ``repro.check.verifier``),
so that it imports nothing of the simulator.  :func:`replay_lane` walks
one lane's op rows under that lane's effective configuration and
returns, per op, the legality bit, the host, dummy and erase deltas,
the pages the op moved and the zone's LUN columns, and, at the end,
the whole device state.  Both allocation policies are modelled.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from .elements import ElementKind
from .legacy import AVAIL_ALLOCATED, AVAIL_FREE, AVAIL_INVALID, AVAIL_VALID

_BIG = 2**30  # sentinel wear for unavailable slots

OP_NOP, OP_ALLOC, OP_WRITE, OP_FINISH, OP_RESET, OP_READ = range(6)
F_HOST = 1
ZONE_EMPTY, ZONE_OPEN, ZONE_FULL = 0, 1, 2
POLICY_TRADITIONAL, POLICY_SILENT = 0, 1

ERR_FULL = "full"
ERR_OVERFLOW = "overflow"
ERR_ACTIVE_LIMIT = "active-limit"
ERR_ALLOC_INFEASIBLE = "alloc-infeasible"


class Lane:
    """One lane's effective configuration values as attributes."""

    def __init__(self, values: Dict):
        self.__dict__.update(values)


class _Model:
    """Numpy mirror of the engine state machine for ONE lane (one
    program under one effective dyn).  Method structure shadows the
    engine's ``_alloc`` / ``_grow_silent`` / ``_write`` / ``_finish``
    / ``_reset`` transitions; every formula is a transliteration, so a
    semantic change engine-side shows up as an ok-mask mismatch in the
    differential fuzz tests rather than silently here."""

    def __init__(self, cfg, dv: Lane):
        self.cfg = cfg
        self.dv = dv
        n = cfg.n_elements
        self.ng = max(dv.n_elements // max(dv.per_group, 1), 1)
        self.wear = np.zeros(n, np.int64)
        self.avail = np.full(n, AVAIL_FREE, np.int64)
        self.pages = np.zeros(n, np.int64)
        self.ezone = np.full(n, -1, np.int64)
        self.zone_state = np.full(cfg.n_zones, ZONE_EMPTY, np.int64)
        self.zone_wp = np.zeros(cfg.n_zones, np.int64)
        self.zone_host_wp = np.zeros(cfg.n_zones, np.int64)
        self.zone_elems = np.full((cfg.n_zones, cfg.n_slots), -1, np.int64)
        self.zone_cols = np.zeros((cfg.n_zones, cfg.parallelism), np.int64)
        self.rr_next = 0
        self.n_active = 0
        self.host_pages = 0
        self.dummy_pages = 0
        # derived (value-level) geometry, exactly as the engine computes
        # it from the lane's DynConfig
        self.n_slots_eff = dv.zone_pages // dv.pages_per_element
        self.take_eff = int(np.clip(
            self.n_slots_eff // max(dv.slot_stride, 1), 1, dv.take))
        self.block_erases = 0

    # -- selection helpers (numpy twins of the engine's) --------------- #
    def _grids(self):
        n = self.cfg.n_elements
        w2 = self.wear[:n].reshape(self.cfg.n_groups, self.cfg.per_group)
        a2 = self.avail[:n].reshape(self.cfg.n_groups, self.cfg.per_group)
        return w2, a2

    def _rr_mask(self, start: int) -> np.ndarray:
        elig = np.zeros(self.cfg.n_groups, bool)
        for pos in range(min(self.dv.zone_groups, self.cfg.zone_groups)):
            elig[(start + pos) % self.ng] = True
        return elig

    def _take_lowest(self, w2, a2, elig, by_wear: bool, take_eff: int):
        cfg, dv = self.cfg, self.dv
        col = np.arange(cfg.per_group, dtype=np.int64)[None, :]
        free = ((a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID))
        free = free & elig[:, None] & (col < dv.per_group)
        composite = w2 * cfg.per_group + col
        key = np.where(free,
                       composite if by_wear
                       else np.broadcast_to(col, w2.shape),
                       _BIG)
        cols = np.argsort(key, axis=1, kind="stable")[:, : cfg.take]
        kth = np.take_along_axis(key, cols, axis=1)[:, take_eff - 1]
        feasible = bool(np.all((kth < _BIG) | ~elig))
        # first-fit claims the take_eff lowest columns: only those are
        # ranked by (wear, col) into the zone's slots
        rank = np.arange(cfg.take, dtype=np.int64)[None, :]
        sel_free = np.take_along_axis(free, cols, axis=1) & (rank < take_eff)
        sel_key = np.where(
            sel_free,
            np.take_along_axis(w2, cols, axis=1) * cfg.per_group + cols,
            _BIG)
        order = np.argsort(sel_key, axis=1, kind="stable")
        cols = np.take_along_axis(cols, order, axis=1)
        return cols, feasible

    def _cheapest_groups(self, w2, a2, take_eff: int) -> np.ndarray:
        cfg, dv = self.cfg, self.dv
        grow = np.arange(cfg.n_groups, dtype=np.int64)[:, None]
        col = np.arange(cfg.per_group, dtype=np.int64)[None, :]
        ok = ((a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID))
        ok = ok & (grow < self.ng) & (col < dv.per_group)
        keyed = np.where(ok, w2.astype(np.float32), np.float32(np.inf))
        part = np.sort(keyed, axis=1)[:, : cfg.take]
        rank = np.arange(cfg.take)[None, :]
        cost = np.where(rank < take_eff, part,
                        np.float32(0.0)).sum(axis=1, dtype=np.float32)
        order = np.argsort(cost, kind="stable")[: cfg.zone_groups]
        picked = np.arange(cfg.zone_groups) < dv.zone_groups
        elig = np.zeros(cfg.n_groups, bool)
        elig[order[picked]] = True
        return elig

    def _wear_bounded(self, w2, a2, bound: Optional[int] = None):
        cfg, dv = self.cfg, self.dv
        bound = dv.wear_bound if bound is None else bound
        grow = np.arange(cfg.n_groups, dtype=np.int64)[:, None]
        col = np.arange(cfg.per_group, dtype=np.int64)[None, :]
        free = ((a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID))
        free = free & (grow < self.ng) & (col < dv.per_group)
        min_wear = int(w2[free].min()) if free.any() else _BIG
        in_bound = (w2 - min_wear) <= bound
        return np.where(in_bound, a2, AVAIL_VALID)

    def _win(self, elig: np.ndarray) -> np.ndarray:
        idx = np.nonzero(elig)[0]
        out = np.zeros(self.cfg.zone_groups, np.int64)
        out[: min(len(idx), self.cfg.zone_groups)] = \
            idx[: self.cfg.zone_groups]
        return out

    def _written_per_slot(self, wp: int) -> np.ndarray:
        cfg, dv = self.cfg, self.dv
        P, ppb = cfg.parallelism, cfg.pages_per_block
        seg = np.arange(cfg.n_segments, dtype=np.int64)
        seg_pages = P * ppb
        w_seg = np.clip(wp - seg * seg_pages, 0, seg_pages)
        col = np.arange(P, dtype=np.int64)
        blk = np.clip((w_seg[:, None] - col[None, :] + P - 1) // P,
                      0, ppb)
        lpg = P // dv.zone_groups
        seg_span = dv.pages_per_element // (lpg * ppb)
        slot = ((seg[:, None] // seg_span) * dv.slot_stride
                + col[None, :] // lpg)
        out = np.zeros(cfg.n_slots, np.int64)
        keep = slot.reshape(-1) < cfg.n_slots  # masked scatters drop
        np.add.at(out, slot.reshape(-1)[keep], blk.reshape(-1)[keep])
        return out

    # -- transitions ---------------------------------------------------- #
    def _alloc(self, zone: int, hint: int) -> Tuple[bool, Optional[str],
                                                    Optional[str]]:
        """Mirror of engine ``_alloc``; applies effects when ok.
        Returns (ok, error class, shim message) for the failure case."""
        cfg, dv = self.cfg, self.dv
        limit_ok = self.n_active < dv.max_active

        if cfg.kind is ElementKind.FIXED:
            free = ((self.avail == AVAIL_FREE)
                    | (self.avail == AVAIL_INVALID))
            key = np.where(
                free,
                self.wear if dv.wear_aware
                else np.arange(cfg.n_elements, dtype=np.int64),
                _BIG)
            e = int(np.argmin(key))
            feasible = bool(free.any())
            band = e % cfg.n_groups
            cols_row = (band * cfg.parallelism
                        + np.arange(cfg.parallelism, dtype=np.int64))
            claimed_ids = np.asarray([e], np.int64)
            elems_row = np.full(cfg.n_slots, e, np.int64)
            rr_next = self.rr_next
        else:
            w2, a2 = self._grids()
            if dv.alloc_policy == POLICY_SILENT:
                per_rank = dv.pages_per_element * dv.zone_groups
                ranks_hint = -(-hint // max(per_rank, 1))
                take_s = int(np.clip(ranks_hint if hint > 0
                                     else self.take_eff,
                                     1, self.take_eff))
                a2b = self._wear_bounded(w2, a2)
                elig = self._cheapest_groups(w2, a2b, take_s)
                cols, feasible = self._take_lowest(w2, a2b, elig, True,
                                                   take_s)
                rr_next = self.rr_next
                rank_lim = take_s
            else:
                elig = self._rr_mask(self.rr_next)
                cols, f1 = self._take_lowest(w2, a2, elig,
                                             dv.wear_aware,
                                             self.take_eff)
                feasible = f1
                if not f1:
                    elig = self._cheapest_groups(w2, a2, self.take_eff)
                    cols, f2 = self._take_lowest(w2, a2, elig, True,
                                                 self.take_eff)
                    feasible = f2
                rr_next = (self.rr_next + dv.zone_groups) % self.ng
                rank_lim = dv.take

            win = self._win(elig)
            eids = win[:, None] * cfg.per_group + cols[win]
            ranks = np.arange(cfg.take, dtype=np.int64)[None, :]
            cpos = np.arange(cfg.zone_groups, dtype=np.int64)[:, None]
            valid = cpos < dv.zone_groups
            raw_slots = ranks * dv.slot_stride + cpos
            claimed = (valid & (raw_slots < self.n_slots_eff)
                       & (ranks < rank_lim))
            elems_row = np.full(cfg.n_slots, -1, np.int64)
            elems_row[raw_slots[claimed]] = eids[claimed]
            claimed_ids = eids[claimed].reshape(-1)
            lpg = cfg.parallelism // dv.zone_groups
            c = np.arange(cfg.parallelism, dtype=np.int64)
            pos = np.clip(c // lpg, 0, cfg.zone_groups - 1)
            cols_row = win[pos] * lpg + c % lpg

        ok = bool(limit_ok and feasible)
        if ok:
            inv = self.avail[claimed_ids] == AVAIL_INVALID
            self.wear[claimed_ids] += inv.astype(np.int64)
            self.erase_count(int(inv.sum()))
            self.avail[claimed_ids] = AVAIL_ALLOCATED
            self.pages[claimed_ids] = 0
            self.ezone[claimed_ids] = zone
            self.zone_state[zone] = ZONE_OPEN
            self.zone_wp[zone] = 0
            self.zone_host_wp[zone] = 0
            self.zone_elems[zone] = elems_row
            self.zone_cols[zone] = cols_row
            self.n_active += 1
        if limit_ok:  # rr advance survives an infeasible attempt
            self.rr_next = rr_next
        if ok:
            return True, None, None
        if not limit_ok:
            return False, ERR_ACTIVE_LIMIT, (
                f"open/active zone limit ({dv.max_active}) reached")
        return False, ERR_ALLOC_INFEASIBLE, (
            f"no free storage elements for zone {zone} "
            f"(spec)")

    def erase_count(self, n_invalid: int) -> None:
        self.block_erases += n_invalid * (
            self.dv.pages_per_element // self.cfg.pages_per_block)

    def _grow(self, zone: int, wp1: int, pred: bool) -> bool:
        """Mirror of engine ``_grow_silent``."""
        cfg, dv = self.cfg, self.dv
        if cfg.kind is ElementKind.FIXED:
            return True
        per_rank = dv.pages_per_element * dv.zone_groups
        need = int(np.clip(-(-wp1 // max(per_rank, 1)), 1, self.take_eff))
        have = int((self.zone_elems[zone] >= 0).sum()
                   // max(dv.zone_groups, 1))
        if not (pred and dv.alloc_policy == POLICY_SILENT
                and need > have):
            return True
        w2, a2 = self._grids()
        a2b = self._wear_bounded(w2, a2)
        lpg = cfg.parallelism // dv.zone_groups
        pos = np.arange(cfg.zone_groups, dtype=np.int64)
        win_g = self.zone_cols[zone][
            np.clip(pos * lpg, 0, cfg.parallelism - 1)] // lpg
        elig = np.zeros(cfg.n_groups, bool)
        elig[win_g[pos < dv.zone_groups]] = True
        k = need - have
        cols, fg = self._take_lowest(w2, a2b, elig, True, k)
        if not fg:
            return False
        win = self._win(elig)
        eids = win[:, None] * cfg.per_group + cols[win]
        ranks = np.arange(cfg.take, dtype=np.int64)[None, :]
        cpos = np.arange(cfg.zone_groups, dtype=np.int64)[:, None]
        raw_slots = (have + ranks) * dv.slot_stride + cpos
        claimed = ((cpos < dv.zone_groups) & (ranks < k)
                   & (raw_slots < self.n_slots_eff))
        self.zone_elems[zone][raw_slots[claimed]] = eids[claimed]
        ids = eids[claimed].reshape(-1)
        inv = self.avail[ids] == AVAIL_INVALID
        self.wear[ids] += inv.astype(np.int64)
        self.erase_count(int(inv.sum()))
        self.avail[ids] = AVAIL_ALLOCATED
        self.pages[ids] = 0
        self.ezone[ids] = zone
        return True

    def _write(self, zone: int, n_pages: int, host: bool
               ) -> Tuple[bool, Optional[str], Optional[str]]:
        dv = self.dv
        zst0 = self.zone_state[zone]
        aok, aerr, amsg = True, None, None
        if zst0 == ZONE_EMPTY:
            # the implicit ALLOC persists even if the write then fails
            aok, aerr, amsg = self._alloc(zone, hint=n_pages)
        wp0 = int(self.zone_wp[zone])
        wp1 = wp0 + n_pages
        fits = wp1 <= dv.zone_pages
        gok = self._grow(zone, wp1,
                         bool(zst0 != ZONE_FULL and aok and fits))
        ok = bool(zst0 != ZONE_FULL and aok and fits and gok)
        if ok:
            written = self._written_per_slot(wp1)
            elems = self.zone_elems[zone]
            valid = elems >= 0
            touched = valid & (written > 0)
            self.pages[elems[valid]] = written[valid]
            self.avail[elems[touched]] = AVAIL_VALID
            self.zone_wp[zone] = wp1
            self.zone_host_wp[zone] += n_pages if host else 0
            seal = wp1 == dv.zone_pages
            self.zone_state[zone] = (ZONE_FULL if seal
                                     else ZONE_OPEN)
            self.n_active -= int(seal)
            self.host_pages += n_pages if host else 0
            self.dummy_pages += 0 if host else n_pages
            return True, None, None
        # classification follows the shim's raise order: FULL, then the
        # implicit allocation, then overflow, then on-the-fly growth
        if zst0 == ZONE_FULL:
            return False, ERR_FULL, f"write to FULL zone {zone}"
        if not aok:
            return False, aerr, amsg
        if not fits:
            return False, ERR_OVERFLOW, (
                f"zone {zone} overflow: wp={wp0} + {n_pages} "
                f"> {dv.zone_pages}")
        return False, ERR_ALLOC_INFEASIBLE, (
            f"no free storage elements for zone {zone} "
            f"(spec)")

    def _finish(self, zone: int) -> int:
        """Mirror of engine ``_finish``; returns the dummy padding the
        seal emitted (0 for FULL/EMPTY zones).  Always ok."""
        dv = self.dv
        zst0 = self.zone_state[zone]
        if zst0 == ZONE_FULL:
            return 0
        is_open = zst0 == ZONE_OPEN
        wp = int(self.zone_wp[zone])
        written = self._written_per_slot(wp)
        elems = self.zone_elems[zone]
        valid = elems >= 0
        untouched = valid & (written == 0) & is_open
        touched = valid & (written > 0) & is_open
        cap = dv.pages_per_element
        pad = int(np.where(touched, cap - written, 0).sum())
        u = elems[untouched]
        t = elems[touched]
        self.avail[u] = AVAIL_FREE
        self.pages[u] = 0
        self.ezone[u] = -1
        self.avail[t] = AVAIL_VALID
        self.pages[t] = cap
        self.zone_elems[zone][untouched] = -1
        self.zone_state[zone] = ZONE_FULL
        self.dummy_pages += pad
        self.n_active -= int(is_open)
        return pad

    def _reset(self, zone: int) -> None:
        zst0 = self.zone_state[zone]
        elems = self.zone_elems[zone]
        ids = elems[elems >= 0]
        cur = self.avail[ids]
        self.avail[ids] = np.where(
            cur == AVAIL_VALID, AVAIL_INVALID,
            np.where(cur == AVAIL_ALLOCATED, AVAIL_FREE, cur))
        self.ezone[ids] = -1
        self.pages[ids] = 0
        self.zone_state[zone] = ZONE_EMPTY
        self.zone_wp[zone] = 0
        self.zone_host_wp[zone] = 0
        self.zone_elems[zone] = -1
        self.zone_cols[zone] = 0
        self.n_active -= int(zst0 == ZONE_OPEN)

    # -- op dispatch ---------------------------------------------------- #
    def apply(self, row) -> Tuple[bool, int]:
        """One op row -> (engine ok bit, dummy pages a FINISH padded)."""
        op = int(row[0])
        opc = min(max(op, 0), OP_READ)  # the engine's clip
        zone = int(np.clip(row[1], 0, self.dv.n_zones - 1))
        n_pages = int(row[2])
        host = bool(int(row[3]) & F_HOST)
        ok, pad = True, 0
        if opc == OP_ALLOC:
            if self.zone_state[zone] == ZONE_EMPTY:
                ok = self._alloc(zone, hint=n_pages)[0]
            # non-EMPTY: no-op, ok (and no round-robin consumption)
        elif opc == OP_WRITE:
            ok = self._write(zone, n_pages, host)[0]
        elif opc == OP_FINISH:
            pad = self._finish(zone)
        elif opc == OP_RESET:
            self._reset(zone)
        return ok, pad


def replay_lane(static, values: Dict, program: np.ndarray) -> Dict:
    """Walk one lane's ``(n_ops, >=4)`` op rows through the model.

    Returns per-op arrays ``ok``, ``host_delta``, ``dummy_delta``,
    ``erase_delta``, ``pages`` (pages the op physically moved: write
    advance, FINISH padding, READ transfers) and ``cols`` (the zone's
    column -> LUN map after the op), and the final ``state``."""
    m = _Model(static, Lane(values))
    program = np.asarray(program)
    n = len(program)
    ok = np.ones(n, bool)
    host_d = np.zeros(n, np.int64)
    dummy_d = np.zeros(n, np.int64)
    erase_d = np.zeros(n, np.int64)
    pages = np.zeros(n, np.int64)
    cols = np.zeros((n, static.parallelism), np.int64)
    for i, row in enumerate(program):
        if int(row[0]) == OP_NOP:
            continue
        h0, d0, e0 = m.host_pages, m.dummy_pages, m.block_erases
        zone = int(np.clip(row[1], 0, m.dv.n_zones - 1))
        wp0 = int(m.zone_wp[zone])
        ok[i], pad = m.apply(row)
        host_d[i] = m.host_pages - h0
        dummy_d[i] = m.dummy_pages - d0
        erase_d[i] = m.block_erases - e0
        op = int(row[0])
        pages[i] = (max(int(m.zone_wp[zone]) - wp0, 0)
                    + (pad if op == OP_FINISH else 0)
                    + (int(row[2]) if op == OP_READ else 0))
        cols[i] = m.zone_cols[zone]
    n_el = static.n_elements
    state = {
        "elem_wear": m.wear[:n_el], "elem_avail": m.avail[:n_el],
        "elem_pages": m.pages[:n_el], "elem_zone": m.ezone[:n_el],
        "zone_state": m.zone_state, "zone_wp": m.zone_wp,
        "zone_host_wp": m.zone_host_wp, "zone_elems": m.zone_elems,
        "zone_cols": m.zone_cols, "rr_next": m.rr_next,
        "n_active": m.n_active, "host_pages": m.host_pages,
        "dummy_pages": m.dummy_pages, "block_erases": m.block_erases,
    }
    return {"ok": ok, "host_delta": host_d, "dummy_delta": dummy_d,
            "erase_delta": erase_d, "pages": pages, "cols": cols,
            "state": state}
