"""Vectorized JAX zone allocator (paper §5, Eqs. 1-6).

All element layouts in :mod:`repro.core.elements` are *group-major with a
fixed per-group count*, so the allocator views the device as a dense
``(n_groups, per_group)`` wear/availability matrix and the balanced ILP
solution is a masked per-row top-G selection:

    for each eligible group g: take the ``take`` lowest-wear available
    elements of row g.

This is exactly the computation the Pallas ``zns_alloc`` kernel implements
on TPU (rows tiled into VMEM); here we provide the jit'd XLA fallback that
the emulator uses on CPU, plus the round-robin eligible-group rotation the
paper uses to spread consecutive zones across LUNs (Eq. 6).

The general (unbalanced) ILP is handled by :mod:`repro.core.alloc_exact`;
hypothesis tests assert this fast path matches the exact DP wherever the
balanced form applies (every configuration evaluated in the paper).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alloc_exact import ALLOCATABLE

_BIG = jnp.array(2**30, jnp.int32)  # sentinel wear for unavailable slots


@functools.partial(jax.jit, static_argnames=("take",))
def select_lowest_wear(wear2d: jax.Array,
                       avail2d: jax.Array,
                       eligible: jax.Array,
                       take: int) -> Tuple[jax.Array, jax.Array]:
    """Masked per-group lowest-wear selection.

    Args:
      wear2d:   (n_groups, per_group) int32 erase counts.
      avail2d:  (n_groups, per_group) int32 availability codes.
      eligible: (n_groups,) bool -- groups allowed to contribute (Eq. 6).
      take:     elements to take per eligible group (static).

    Returns:
      sel:      (n_groups, per_group) bool selection mask.
      feasible: () bool -- every eligible group had >= take available.
    """
    allocatable = (avail2d == ALLOCATABLE[0]) | (avail2d == ALLOCATABLE[1])
    allocatable = allocatable & eligible[:, None]
    keyed = jnp.where(allocatable, wear2d, _BIG)
    # rank of each slot within its row by (wear, index) -- stable
    order = jnp.argsort(keyed, axis=1, stable=True)
    ranks = jnp.argsort(order, axis=1, stable=True)
    sel = (ranks < take) & allocatable
    feasible = jnp.all(jnp.where(eligible,
                                 jnp.sum(allocatable, axis=1) >= take,
                                 True))
    return sel, feasible


@functools.partial(jax.jit, static_argnames=("take",))
def selection_cost(wear2d: jax.Array, sel: jax.Array, take: int) -> jax.Array:
    del take
    return jnp.sum(jnp.where(sel, wear2d, 0))


def eligible_mask(n_groups: int, start: int, span: int) -> np.ndarray:
    """Round-robin eligible-group window (paper Eq. 6): ``span`` adjacent
    groups starting at ``start`` (mod n_groups)."""
    idx = (start + np.arange(span)) % n_groups
    mask = np.zeros(n_groups, dtype=bool)
    mask[idx] = True
    return mask


class RoundRobin:
    """Rotates the eligible-group window between consecutive allocations so
    consecutive zones land on disjoint LUNs where possible (paper §5)."""

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

    def reset(self) -> None:
        self._next = 0


def allocate(wear2d: np.ndarray,
             avail2d: np.ndarray,
             eligible: np.ndarray,
             take: int,
             *,
             impl: str = "xla") -> Tuple[np.ndarray, bool]:
    """Host-facing allocation entry point.

    ``impl``: 'xla' (jit fallback) or 'pallas' (TPU kernel via
    :mod:`repro.kernels.zns_alloc.ops`; compiled for the TPU).
    Returns (selection mask (n_groups, per_group), feasible).
    """
    if impl == "pallas":
        from repro.kernels.zns_alloc import ops as _ops
        sel, feasible = _ops.zns_alloc(
            jnp.asarray(wear2d, jnp.int32),
            jnp.asarray(avail2d, jnp.int32),
            jnp.asarray(eligible),
            take=take)
    else:
        sel, feasible = select_lowest_wear(
            jnp.asarray(wear2d, jnp.int32),
            jnp.asarray(avail2d, jnp.int32),
            jnp.asarray(eligible),
            take=take)
    return np.asarray(sel), bool(feasible)
