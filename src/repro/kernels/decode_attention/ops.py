"""Public decode-attention entry point with implementation switch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas)
from repro.kernels.decode_attention.ref import decode_attention_ref

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                             lengths: jax.Array, *,
                             block_s: int = 1024) -> jax.Array:
    """Streaming-softmax over kv blocks in plain jnp (XLA-compilable
    everywhere; same math as the kernel)."""
    b, hq, d = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    bs = min(block_s, s)
    if s % bs:
        raise ValueError(f"cache len {s} % block {bs} != 0")
    ns = s // bs
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32) / (d ** 0.5)
    kf = k.reshape(b, ns, bs, hkv, d).transpose(1, 0, 2, 3, 4)
    vf = v.reshape(b, ns, bs, hkv, d).transpose(1, 0, 2, 3, 4)

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kb, vb, si = blk
        sblk = jnp.einsum("bhgd,bkhd->bhgk", qf, kb.astype(jnp.float32))
        pos = si * bs + jnp.arange(bs)
        mask = pos[None, :] < lengths[:, None]           # (B, BS)
        sblk = jnp.where(mask[:, None, None], sblk, NEG_INF)
        m_cur = jnp.max(sblk, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sblk - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  (kf, vf, jnp.arange(ns)))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).reshape(b, hq, d).astype(q.dtype)


def decode_attention(q, k, v, lengths, *, impl: str = "chunked",
                     block_s: int = 512, interpret: bool = False):
    if impl == "pallas":
        return decode_attention_pallas(q, k, v, lengths, block_s=block_s,
                                       interpret=interpret)
    if impl == "chunked":
        return decode_attention_chunked(q, k, v, lengths,
                                        block_s=max(block_s, 1024))
    if impl == "xla":
        return decode_attention_ref(q, k, v, lengths)
    raise ValueError(f"unknown decode attention impl: {impl}")
