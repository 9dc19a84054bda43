"""Public selective-scan entry point + single-step decode form."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_pallas


def ssm_scan(x, dt, b, c, a, d, *, impl: str = "ref", chunk: int = 256,
             interpret: bool = False):
    """x: (BH, T, P); dt: (BH, T, P); b/c: (BH, T, N); a: (P, N); d: (P,)."""
    if impl == "pallas":
        return ssm_scan_pallas(x, dt, b, c, a, d, chunk=chunk,
                               interpret=interpret)
    if impl == "ref":
        return ssm_scan_ref(x, dt, b, c, a, d)
    raise ValueError(f"unknown ssm impl: {impl}")


@jax.jit
def single_step(h, x_t, dt_t, b_t, c_t, a, d):
    """One decode step: h (BH, P, N) -> (h', y) -- O(P*N) per token.

    x_t: (BH, P); dt_t: (BH, P); b_t/c_t: (BH, N).
    """
    af = a.astype(jnp.float32)
    da = jnp.exp(dt_t[..., None].astype(jnp.float32) * af[None])
    h = h * da + (dt_t * x_t).astype(jnp.float32)[..., None] \
        * b_t.astype(jnp.float32)[:, None, :]
    y = jnp.sum(h * c_t.astype(jnp.float32)[:, None, :], axis=-1) \
        + d.astype(jnp.float32)[None] * x_t.astype(jnp.float32)
    return h, y.astype(x_t.dtype)
