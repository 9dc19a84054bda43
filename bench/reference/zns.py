"""Write-order striping math (paper §3 Fig. 3b, §4), in numpy.

The benchmark's own copy of the simulator's striping math.

A zone spans P LUN *columns* and ``n_segments`` stacked *segments*; pages
are striped round-robin across the P columns of the current segment, and a
segment is fully written before the write pointer advances to the next
(paper Fig. 3b).  These closed forms convert a zone write pointer ``wp``
(pages written so far) into per-block / per-element page counts -- the
quantity FINISH needs to decide dummy padding.

Element-slot ordering convention (used by the device mapping table):

* BLOCK       slot = seg * P + col
* VCHUNK(s)   slot = seg * (P//s) + col//s
* HCHUNK(s)   slot = (seg//s) * P + col
* SUPERBLOCK  slot = seg
* FIXED       slot = 0
"""

from __future__ import annotations

import numpy as np

from .elements import ElementKind, ElementSpec


def pages_per_block(wp: int, parallelism: int, n_segments: int,
                    pages_per_blk: int) -> np.ndarray:
    """Pages written in each (segment, column) erase block at pointer wp.

    Returns int64 array of shape (n_segments, parallelism).
    """
    P = parallelism
    seg_pages = P * pages_per_blk
    seg = np.arange(n_segments, dtype=np.int64)
    w_seg = np.clip(wp - seg * seg_pages, 0, seg_pages)  # pages in each seg
    col = np.arange(P, dtype=np.int64)
    # pages in column c of a segment with w pages striped round-robin:
    # ceil((w - c) / P) clipped to [0, pages_per_blk]
    cnt = (w_seg[:, None] - col[None, :] + P - 1) // P
    return np.clip(cnt, 0, pages_per_blk)


def element_pages(wp: int, spec: ElementSpec, parallelism: int,
                  n_segments: int, pages_per_blk: int) -> np.ndarray:
    """Pages written per element *slot* (see module docstring ordering)."""
    blk = pages_per_block(wp, parallelism, n_segments, pages_per_blk)
    P = parallelism
    if spec.kind is ElementKind.BLOCK:
        return blk.reshape(-1)
    if spec.kind is ElementKind.VCHUNK:
        s = spec.chunk
        return blk.reshape(n_segments, P // s, s).sum(axis=2).reshape(-1)
    if spec.kind is ElementKind.SUPERBLOCK:
        return blk.sum(axis=1)
    if spec.kind is ElementKind.HCHUNK:
        s = spec.chunk
        if n_segments % s:
            raise ValueError("hchunk span must divide n_segments")
        return blk.reshape(n_segments // s, s, P).sum(axis=1).reshape(-1)
    if spec.kind is ElementKind.FIXED:
        return np.asarray([blk.sum()], dtype=np.int64)
    raise ValueError(spec.kind)


def n_slots(spec: ElementSpec, parallelism: int, n_segments: int) -> int:
    if spec.kind is ElementKind.BLOCK:
        return n_segments * parallelism
    if spec.kind is ElementKind.VCHUNK:
        return n_segments * (parallelism // spec.chunk)
    if spec.kind is ElementKind.SUPERBLOCK:
        return n_segments
    if spec.kind is ElementKind.HCHUNK:
        return (n_segments // spec.chunk) * parallelism
    if spec.kind is ElementKind.FIXED:
        return 1
    raise ValueError(spec.kind)


def slot_of_group_rank(spec: ElementSpec, parallelism: int, n_segments: int,
                       col_or_band: int, rank: int) -> int:
    """Map (which column/band within the zone, rank-th element taken from
    that group) -> element slot.  Rank runs over the ``take`` elements a
    group contributes, assigned to segments bottom-up."""
    P = parallelism
    if spec.kind is ElementKind.BLOCK:
        return rank * P + col_or_band          # seg=rank, col
    if spec.kind is ElementKind.VCHUNK:
        return rank * (P // spec.chunk) + col_or_band
    if spec.kind is ElementKind.SUPERBLOCK:
        return rank                             # seg=rank
    if spec.kind is ElementKind.HCHUNK:
        return rank * P + col_or_band           # seggrp=rank, col
    if spec.kind is ElementKind.FIXED:
        return 0
    raise ValueError(spec.kind)
