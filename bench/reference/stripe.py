"""Tenant programs to per-member op rows, in plain numpy.

The benchmark's own copy of the fleet builder's row transforms
(``repro.fleet.tenants`` and the RAID-5 stripe math of
``repro.array.raid``): tag each tenant's rows, merge the tenants
round-robin by position, stripe the logical superzone program over the
array's members at chunk granularity with log-structured parity, and
pad the lanes with NOP rows.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from .model import F_HOST, OP_FINISH, OP_RESET, OP_WRITE


def tag(program: np.ndarray, tenant: int) -> np.ndarray:
    out = np.zeros((len(program), 5), dtype=np.int32)
    out[:, :4] = np.asarray(program, dtype=np.int32)[:, :4]
    out[:, 4] = tenant
    return out


def interleave(programs: Sequence[np.ndarray]) -> np.ndarray:
    """Round-robin by per-tenant position: row i of every tenant before
    row i + 1 of any."""
    programs = [p for p in programs if len(p)]
    keys = np.concatenate([np.arange(len(p)) * len(programs) + i
                           for i, p in enumerate(programs)])
    return np.concatenate(programs)[np.argsort(keys, kind="stable")]


def parity_member(zone: int, stripe: int, n_devices: int) -> int:
    return (zone + stripe) % n_devices


def stripe(program: np.ndarray, *, n_devices: int, chunk_pages: int,
           parity: bool, member_zone_pages: int, parity_tenant: int
           ) -> List[np.ndarray]:
    """One row list per member: data chunks to the stripe's data
    members, each completed stripe's parity chunk to its rotating
    parity member before the next append, a final partial stripe's
    parity at FINISH, FINISH/RESET/ALLOC/READ on every member."""
    n_data = n_devices - (1 if parity else 0)
    cap, c = n_data * member_zone_pages, chunk_pages
    out: List[list] = [[] for _ in range(n_devices)]
    wp, done = {}, {}

    def parity_upto(zone: int, upto: int) -> None:
        while parity and done.get(zone, 0) < upto:
            s = done.get(zone, 0)
            out[parity_member(zone, s, n_devices)].append(
                (OP_WRITE, zone, c, F_HOST, parity_tenant))
            done[zone] = s + 1

    for op, zone, n_pages, flags, tenant in np.asarray(program).tolist():
        if op == OP_WRITE:
            page = wp.get(zone, 0)
            if page + n_pages > cap:
                raise ValueError(f"superzone {zone} overflow")
            left = n_pages
            while left > 0:
                s, off = divmod(page, c * n_data)
                slot, r = divmod(off, c)
                dev = slot
                if parity and slot >= parity_member(zone, s, n_devices):
                    dev = slot + 1
                parity_upto(zone, s)
                take = min(c - r, left)
                out[dev].append((op, zone, take, flags, tenant))
                page += take
                left -= take
            wp[zone] = page
            parity_upto(zone, page // (c * n_data))
        elif op == OP_FINISH:
            page = wp.get(zone, 0)
            full = page // (c * n_data)
            parity_upto(zone, full)
            if parity and page % (c * n_data) and done.get(zone, 0) <= full:
                out[parity_member(zone, full, n_devices)].append(
                    (OP_WRITE, zone, c, F_HOST, parity_tenant))
                done[zone] = full + 1
            for d in range(n_devices):
                out[d].append((op, zone, 0, 0, tenant))
        elif op == OP_RESET:
            for d in range(n_devices):
                out[d].append((op, zone, 0, 0, tenant))
            wp.pop(zone, None)
            done.pop(zone, None)
        else:
            for d in range(n_devices):
                out[d].append((op, zone, n_pages, flags, tenant))
    return [np.asarray(rows, dtype=np.int32).reshape(-1, 5) for rows in out]


def pad(lanes: Sequence[np.ndarray], n_ops: int) -> np.ndarray:
    out = np.zeros((len(lanes), n_ops, 5), dtype=np.int32)
    for i, p in enumerate(lanes):
        out[i, : len(p)] = p
    return out
