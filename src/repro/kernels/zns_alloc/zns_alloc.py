"""Pallas TPU kernel: wear-minimizing per-LUN top-G selection (paper §5).

The SilentZNS allocator solves, per zone allocation, the balanced form of
the ILP (Eqs. 1-6): for each eligible LUN-group, select the ``take``
lowest-wear *available* storage elements.  Table 4 of the paper shows this
selection is the technique's dominant overhead (up to ~9 ms with MOSEK at
block granularity) -- so we make it a kernel.

TPU mapping
-----------
* The device state is a dense ``(n_groups, per_group)`` wear/availability
  matrix (group-major, fixed per-group width -- guaranteed by
  ``repro.core.elements``).  At fleet scale (one allocator instance
  managing the simulated devices of many hosts) this matrix is far larger
  than VMEM, so the grid tiles *rows* (groups): each grid step streams a
  ``(GB, per_group)`` tile HBM->VMEM.
* Top-G selection is done with G rounds of a masked row-argmin -- an
  MXU-free, VPU-bound loop.  ``G = take`` is static, rows are processed
  vector-parallel, and each round updates the selection mask in VMEM.
  This avoids a full sort (O(W log W) and awkward on TPU) in favor of
  O(G * W) vector min-reductions, which wins for the small G (<= 32) the
  paper's geometries produce.
* Availability codes: elements with a in {0, 3} are allocatable (paper
  §5); ineligible rows produce all-zero selections.

Outputs: ``sel`` (int32 0/1 selection mask) and ``ok`` (per-group count of
allocatable elements, so the host can check feasibility: ok >= take).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 2**30  # python literal: safe to close over in the kernel


def _kernel(wear_ref, avail_ref, elig_ref, sel_ref, ok_ref, *, take: int):
    wear = wear_ref[...]          # (GB, W) int32
    avail = avail_ref[...]        # (GB, W) int32
    elig = elig_ref[...]          # (GB, 1) int32 (0/1)

    allocatable = (avail == 0) | (avail == 3)
    allocatable &= elig != 0
    ok_ref[...] = jnp.sum(allocatable.astype(jnp.int32), axis=1,
                          keepdims=True)

    keyed = jnp.where(allocatable, wear, BIG)
    gb, w = keyed.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (gb, w), 1)

    def round_body(_, carry):
        keyed, sel = carry
        # row-wise (min wear, min index) selection; ties -> lowest index
        row_min = jnp.min(keyed, axis=1, keepdims=True)          # (GB, 1)
        is_min = keyed == row_min
        min_idx = jnp.min(jnp.where(is_min, col, w), axis=1,
                          keepdims=True)                          # (GB, 1)
        pick = (col == min_idx) & (row_min < BIG)
        sel = jnp.where(pick, 1, sel)
        keyed = jnp.where(pick, BIG, keyed)                       # remove
        return keyed, sel

    # an int32 (not bool) selection carry: Mosaic cannot legalize a loop
    # that carries an i1 vector
    sel = jnp.zeros((gb, w), dtype=jnp.int32)
    _, sel = jax.lax.fori_loop(0, take, round_body, (keyed, sel))
    sel_ref[...] = sel


@functools.partial(jax.jit,
                   static_argnames=("take", "group_block", "interpret"))
def zns_alloc_pallas(wear2d: jax.Array, avail2d: jax.Array,
                     eligible: jax.Array, *, take: int,
                     group_block: int = 8,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (sel int32 (n_groups, per_group), ok int32 (n_groups,)).

    The per-group operands travel as ``(n_groups, 1)`` columns: a TPU
    block of a rank-1 array must span it whole or a multiple of 128."""
    n_groups, per_group = wear2d.shape
    gb = min(group_block, n_groups)
    if n_groups % gb:
        raise ValueError(f"n_groups {n_groups} % group_block {gb} != 0")
    grid = (n_groups // gb,)

    kernel = functools.partial(_kernel, take=take)
    sel, ok = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, per_group), lambda g: (g, 0)),
            pl.BlockSpec((gb, per_group), lambda g: (g, 0)),
            pl.BlockSpec((gb, 1), lambda g: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gb, per_group), lambda g: (g, 0)),
            pl.BlockSpec((gb, 1), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, per_group), jnp.int32),
            jax.ShapeDtypeStruct((n_groups, 1), jnp.int32),
        ],
        interpret=interpret,
    )(wear2d.astype(jnp.int32), avail2d.astype(jnp.int32),
      eligible.astype(jnp.int32).reshape(n_groups, 1))
    return sel, ok[:, 0]
