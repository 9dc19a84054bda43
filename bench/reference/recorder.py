"""The recorded traffic in plain Python: KVBench on a ZenFS-like mount.

The benchmark's own copy of the simulator's application front-end
(``repro.storage.lsm``, ``repro.storage.zonefs`` and the recording
backend of ``repro.storage.compile``), so that the op rows a timed call
produces can be rebuilt from the configuration and the seed alone.
:func:`record_kvbench` runs one KVBench instance against a zone window
and returns its op rows ``(op, zone, n_pages, flags, tenant)``; what
does not shape the rows (space-amplification sampling, file-system
statistics) is left out.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

import numpy as np

from .model import F_HOST, OP_ALLOC, OP_FINISH, OP_RESET, OP_WRITE

EMPTY, OPEN, FULL = 0, 1, 2


def kvbench_ops(n_ops: int, seed: int) -> np.ndarray:
    """0 insert, 1 delete, 2 point query, 3 update: 50/10/15/25."""
    rng = np.random.default_rng(seed)
    return rng.choice(4, size=n_ops, p=[0.50, 0.10, 0.15, 0.25])


class Recorder:
    """A zone window that records every accepted command as a row."""

    def __init__(self, *, zone_pages: int, n_zones: int, max_active: int,
                 zone_base: int = 0,
                 class_tenants: Optional[Dict[str, int]] = None):
        self.zone_pages, self.n_zones = zone_pages, n_zones
        self.max_active, self.zone_base = max_active, zone_base
        self.class_tenants = class_tenants
        self.tenant = 0
        self.state = [EMPTY] * n_zones
        self.wp = [0] * n_zones
        self.n_active = 0
        self.rows: List[tuple] = []

    def stream(self, name: str) -> None:
        if self.class_tenants is not None and name in self.class_tenants:
            self.tenant = self.class_tenants[name]

    def _emit(self, op: int, zone: int, n_pages: int, flags: int) -> None:
        self.rows.append((op, self.zone_base + zone, n_pages, flags,
                          self.tenant))

    def write(self, zone: int, n_pages: int) -> None:
        if self.state[zone] == FULL:
            raise RuntimeError(f"write to FULL zone {zone}")
        if self.state[zone] == EMPTY:
            if self.n_active >= self.max_active:
                raise RuntimeError("active zone limit reached")
            self._emit(OP_ALLOC, zone, 0, 0)
            self.state[zone], self.wp[zone] = OPEN, 0
            self.n_active += 1
        if self.wp[zone] + n_pages > self.zone_pages:
            raise RuntimeError(f"zone {zone} overflow")
        self._emit(OP_WRITE, zone, n_pages, F_HOST)
        self.wp[zone] += n_pages
        if self.wp[zone] == self.zone_pages:
            self.state[zone] = FULL
            self.n_active -= 1

    def finish(self, zone: int) -> None:
        if self.state[zone] == FULL:
            return
        self._emit(OP_FINISH, zone, 0, 0)
        if self.state[zone] == OPEN:
            self.n_active -= 1
        self.state[zone] = FULL

    def reset(self, zone: int) -> None:
        self._emit(OP_RESET, zone, 0, 0)
        if self.state[zone] == OPEN:
            self.n_active -= 1
        self.state[zone], self.wp[zone] = EMPTY, 0

    def program(self) -> np.ndarray:
        return np.asarray(self.rows, dtype=np.int32).reshape(-1, 5)


class ZenFS:
    """Lifetime-hinted placement, one writer per zone, FINISH of a
    victim at ``finish_threshold`` occupancy, RESET of a zone as soon as
    all its data is invalid."""

    def __init__(self, dev: Recorder, finish_threshold: float):
        self.dev, self.threshold = dev, finish_threshold
        self.files: Dict[int, List[list]] = {}     # fid -> [[zone, pages, valid]]
        self.lifetime_of: Dict[int, int] = {}      # fid -> lifetime
        self.sessions: Dict[int, list] = {}        # fid -> [zone, expected]
        self.zone_lifetime: Dict[int, int] = {}
        self.valid: Dict[int, int] = {}
        self.busy: Dict[int, bool] = {}

    def _open(self) -> List[int]:
        return [z for z in range(self.dev.n_zones)
                if self.dev.state[z] == OPEN]

    def _room(self, z: int) -> int:
        return self.dev.zone_pages - self.dev.wp[z]

    def _fresh(self, lifetime: int) -> Optional[int]:
        for z in range(self.dev.n_zones):
            if self.dev.state[z] == EMPTY:
                self.zone_lifetime[z] = lifetime
                return z
        return None

    def _occupancy(self, z: int) -> float:
        return self.dev.wp[z] / self.dev.zone_pages

    def _finish_victim(self) -> Optional[int]:
        best, best_occ = None, -1.0
        for z in self._open():
            occ = self._occupancy(z)
            if (not self.busy.get(z) and occ >= self.threshold
                    and occ > best_occ):
                best, best_occ = z, occ
        if best is not None:
            self.dev.finish(best)
            self._reclaim(best)
        return best

    def _pick(self, lifetime: int, need: int) -> Optional[int]:
        fit = min(need, self.dev.zone_pages)
        for z in self._open():
            if (not self.busy.get(z) and self.zone_lifetime.get(z) == lifetime
                    and self._room(z) >= fit):
                return z
        if len(self._open()) < self.dev.max_active:
            z = self._fresh(lifetime)
            if z is not None:
                return z
        if self._finish_victim() is not None:
            z = self._fresh(lifetime)
            if z is not None:
                return z
        idle = [z for z in self._open()
                if not self.busy.get(z) and self._room(z) > 0]
        if idle:
            return min(idle, key=lambda z: abs(
                self.zone_lifetime.get(z, 0) - lifetime))
        return None

    def begin(self, fid: int, lifetime: int, expected: int = 0) -> None:
        self.files[fid] = []
        self.lifetime_of[fid] = lifetime
        self.sessions[fid] = [None, expected]

    def write(self, fid: int, n_pages: int) -> bool:
        sess = self.sessions[fid]
        left = n_pages
        while left > 0:
            if sess[0] is None or self._room(sess[0]) == 0:
                if sess[0] is not None:
                    self.busy[sess[0]] = False
                z = self._pick(self.lifetime_of[fid], max(left, sess[1]))
                if z is None:
                    return False
                sess[0] = z
                self.busy[z] = True
            z = sess[0]
            chunk = min(self._room(z), left)
            self.dev.write(z, chunk)
            self.valid[z] = self.valid.get(z, 0) + chunk
            self.files[fid].append([z, chunk, True])
            left -= chunk
            sess[1] = max(0, sess[1] - chunk)
            if self._room(z) == 0:
                self.busy[z] = False
        return True

    def end(self, fid: int) -> None:
        sess = self.sessions.pop(fid, None)
        if sess is None or sess[0] is None:
            return
        z = sess[0]
        self.busy[z] = False
        if self.dev.state[z] == OPEN and self._occupancy(z) >= self.threshold:
            self.dev.finish(z)
            self._reclaim(z)

    def delete(self, fid: int) -> None:
        extents = self.files.pop(fid, None)
        if extents is None:
            return
        touched = set()
        for e in extents:
            if e[2]:
                e[2] = False
                self.valid[e[0]] -= e[1]
                touched.add(e[0])
        for z in touched:
            self._reclaim(z)

    def invalidate(self, fid: int, n_pages: int) -> None:
        extents = self.files.get(fid)
        if extents is None:
            return
        left, touched = n_pages, set()
        for e in extents:
            if left <= 0:
                break
            if not e[2] or e[1] == 0:
                continue
            cut = min(e[1], left)
            e[1] -= cut
            self.valid[e[0]] -= cut
            left -= cut
            touched.add(e[0])
        for z in touched:
            self._reclaim(z)

    def _reclaim(self, z: int) -> None:
        st = self.dev.state[z]
        if (st == EMPTY or self.valid.get(z, 0) > 0 or self.busy.get(z)
                or (st == OPEN and self.dev.wp[z] == 0)):
            return
        self.dev.reset(z)
        for d in (self.valid, self.zone_lifetime, self.busy):
            d.pop(z, None)


class KVBench:
    """RocksDB's storage traffic under KVBench: WAL appends per batch of
    mutations, a flush job per full memtable, a compaction job per
    level over its file budget, up to ``max_concurrent_jobs`` jobs
    writing one IO chunk each per round."""

    def __init__(self, fs: ZenFS, cfg: Dict, page_bytes: int):
        self.fs, self.cfg, self.page_bytes = fs, cfg, page_bytes
        self.levels: List[List[list]] = [[] for _ in range(cfg["max_levels"])]
        self.next_fid = 0
        self.memtable = 0
        self.wal: Optional[int] = None
        self.epoch_wals: List[int] = []
        self.pending = collections.deque()
        self.active: List[dict] = []
        self.failed = False

    def _fid(self) -> int:
        self.next_fid += 1
        return self.next_fid

    def _pages(self, entries: int) -> int:
        p = self.page_bytes
        return max(1, (entries * self.cfg["entry_bytes"] + p - 1) // p)

    def _pump(self) -> None:
        while (len(self.active) < self.cfg["max_concurrent_jobs"]
               and self.pending):
            self.active.append(self.pending.popleft())
        still = []
        for job in self.active:
            if not self._step(job):
                self.failed = True
                continue
            if job["idx"] >= len(job["outputs"]):
                job["done"]()
            else:
                still.append(job)
        self.active = still

    def _step(self, job: dict) -> bool:
        fid, lifetime, pages = job["outputs"][job["idx"]]
        self.fs.dev.stream(job["kind"])
        if job["written"] == 0:
            self.fs.begin(fid, lifetime, expected=pages)
        chunk = min(self.cfg["io_chunk_pages"], pages - job["written"])
        if not self.fs.write(fid, chunk):
            self.fs.end(fid)
            return False
        job["written"] += chunk
        if job["written"] >= pages:
            self.fs.end(fid)
            job["idx"] += 1
            job["written"] = 0
        return True

    def run(self, seed: int) -> None:
        cfg = self.cfg
        ops = kvbench_ops(cfg["n_ops"], seed)
        mutations = int((ops != 2).sum())
        batch_max = max(1, cfg["memtable_entries"] // 16)
        done = 0
        while done < mutations and not self.failed:
            batch = min(batch_max, mutations - done)
            done += batch
            if not self._wal_append(batch):
                break
            self.memtable += batch
            if self.memtable >= cfg["memtable_entries"]:
                self._flush()
            self._pump()
        while (self.active or self.pending) and not self.failed:
            self._pump()

    def _wal_append(self, entries: int) -> bool:
        self.fs.dev.stream("wal")
        if self.wal is None:
            self.wal = self._fid()
            self.epoch_wals.append(self.wal)
            self.fs.begin(self.wal, 0)
        ok = self.fs.write(self.wal, self._pages(entries))
        self.failed |= not ok
        return ok

    def _flush(self) -> None:
        entries, self.memtable = self.memtable, 0
        if self.wal is not None:
            self.fs.end(self.wal)
            self.wal = None
        wals, self.epoch_wals = self.epoch_wals, []
        fid = self._fid()

        def done() -> None:
            self.levels[0].append([fid, entries, False])
            for w in wals:
                self.fs.delete(w)
            self._compact(0)

        self.pending.append({"kind": "flush", "idx": 0, "written": 0,
                             "outputs": [(fid, 1, self._pages(entries))],
                             "done": done})

    def _compact(self, level: int) -> None:
        cfg = self.cfg
        if level >= cfg["max_levels"] - 1:
            return
        ready = [s for s in self.levels[level] if not s[2]]
        if len(ready) < cfg["size_ratio"]:
            return
        for s in ready:
            s[2] = True
        entries = sum(s[1] for s in ready)
        merged = int(entries * (1.0 - cfg["dedup_fraction"]))
        fid = self._fid()

        def done() -> None:
            for s in ready:
                self.levels[level].remove(s)
                self.fs.delete(s[0])
            self.levels[level + 1].append([fid, merged, False])
            self._invalidate_deep(level + 1, entries)
            self._compact(level + 1)

        self.pending.append({"kind": "compact", "idx": 0, "written": 0,
                             "outputs": [(fid, 2 + level,
                                          self._pages(merged))],
                             "done": done})

    def _invalidate_deep(self, level: int, merged_entries: int) -> None:
        victims = [s for s in self.levels[level] if not s[2]]
        if not victims:
            return
        per = int(merged_entries * self.cfg["update_overlap"]) // len(victims)
        for s in victims:
            cut = min(per, s[1])
            if cut > 0:
                s[1] -= cut
                self.fs.invalidate(s[0], self._pages(cut))


def record_kvbench(cfg: Dict, seed: int, *, page_bytes: int,
                   zone_pages: int, n_zones: int, max_active: int,
                   finish_threshold: float, zone_base: int = 0,
                   class_tenants: Optional[Dict[str, int]] = None
                   ) -> np.ndarray:
    """The op rows of one KVBench run (``cfg`` holds the KVBench keys
    of a configuration file) on a window of ``n_zones`` zones."""
    dev = Recorder(zone_pages=zone_pages, n_zones=n_zones,
                   max_active=max_active, zone_base=zone_base,
                   class_tenants=class_tenants)
    bench = KVBench(ZenFS(dev, finish_threshold), cfg, page_bytes)
    bench.run(seed)
    if bench.failed:
        raise RuntimeError("KVBench failed to place a file in its window")
    return dev.program()
