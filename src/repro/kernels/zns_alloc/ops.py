"""Jit'd public entry point for the zns_alloc kernel.

``impl='pallas'`` runs the Pallas kernel, compiled for the TPU unless
the caller asks for ``interpret=True`` (the CPU tests do); the jnp
reference is always available via ``impl='ref'``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.zns_alloc.ref import zns_alloc_ref
from repro.kernels.zns_alloc.zns_alloc import zns_alloc_pallas


def _pick_group_block(n_groups: int) -> int:
    # a TPU block's second-minor dim is a multiple of 8 or the whole axis
    return 8 if n_groups % 8 == 0 else n_groups


def zns_alloc(wear2d: jax.Array, avail2d: jax.Array, eligible: jax.Array,
              *, take: int, impl: str = "pallas", interpret: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """Returns (sel bool mask (n_groups, per_group), feasible bool scalar).

    Feasibility = every eligible group has >= take allocatable elements.
    """
    if impl == "ref":
        sel, ok = zns_alloc_ref(wear2d, avail2d, eligible, take=take)
    else:
        sel, ok = zns_alloc_pallas(
            wear2d, avail2d, eligible, take=take,
            group_block=_pick_group_block(wear2d.shape[0]),
            interpret=interpret)
    elig = eligible.astype(bool)
    feasible = jnp.all(jnp.where(elig, ok >= take, True))
    return sel.astype(bool), feasible
