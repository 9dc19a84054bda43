"""Public attention entry point with implementation switch.

* ``pallas``  -- the TPU kernel (``interpret=True`` runs it in the Pallas
  interpreter, as the CPU tests do).
* ``chunked`` -- identical streaming-softmax math written as a
  ``lax.scan`` over kv blocks in plain jnp.  This is what the dry-run and
  the model stack use on CPU: it compiles on every XLA backend, keeps the
  O(S^2) score tensor out of HBM (memory ~ S*BK per head), and reports the
  same FLOPs in cost analysis as the kernel would.
* ``xla``     -- naive full-materialization reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("causal", "block_k"))
def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, block_k: int = 512) -> jax.Array:
    """Streaming-softmax attention as a scan over KV blocks (pure jnp)."""
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    q_per_kv = hq // hkv
    bk = min(block_k, sk)
    sk_valid = sk
    if sk % bk:  # pad the kv length and mask the tail (e.g. 1601 patches)
        pad = bk - sk % bk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        sk = sk + pad
    nk = sk // bk
    scale = 1.0 / (d ** 0.5)

    # (B, Hkv, G, S, D) grouped-query layout; K/V blocks scanned over axis 0
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, q_per_kv, s, d)
    kf = k.reshape(b, hkv, nk, bk, d).transpose(2, 0, 1, 3, 4)
    vf = v.reshape(b, hkv, nk, bk, d).transpose(2, 0, 1, 3, 4)
    rows = jnp.arange(s)[:, None] + (sk - s)  # query absolute positions

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kb, vb, ki = blk                       # (B, Hkv, BK, D)
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        sblk = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb)
        cols = ki * bk + jnp.arange(bk)[None, :]
        if causal:
            mask = (rows >= cols) & (cols < sk_valid)    # (S, BK)
            sblk = jnp.where(mask[None, None, None], sblk, NEG_INF)
        elif sk_valid != sk:
            sblk = jnp.where((cols < sk_valid)[None, None, None],
                             sblk, NEG_INF)
        m_cur = jnp.max(sblk, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sblk - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, q_per_kv, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, q_per_kv, s), jnp.float32)
    acc0 = jnp.zeros((b, hkv, q_per_kv, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (kf, vf, jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]
    return out.reshape(b, hq, s, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q"))
def attention_qchunk(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, block_q: int = 512) -> jax.Array:
    """Scan over *query* blocks with full K/V per block, body rematted.

    The kv-chunk scan ('chunked') carries a running softmax -- reverse-mode
    through it stores O(S^2/BK) residuals.  Query blocks are independent,
    so a scan over q blocks saves only its (small) ys, and jax.checkpoint
    on the body recomputes the (BQ, S) score tile in backward: training
    attention memory drops to O(S * BQ) transient per device.  This is the
    training-path impl; 'chunked' remains for (gradient-free) prefill.
    """
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    bq = min(block_q, s)
    if s % bq:
        raise ValueError(f"seq {s} % block_q {bq} != 0")
    nq = s // bq
    scale = 1.0 / (d ** 0.5)
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, nq, bq, d)
    qf = qf.transpose(3, 0, 1, 2, 4, 5)              # (nq, B, Hkv, G, BQ, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    offset = sk - s                                   # query absolute offset

    @jax.checkpoint
    def body(_, blk):
        qb, qi = blk                                  # (B, Hkv, G, BQ, D)
        sblk = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kf)
        if causal:
            rows = offset + qi * bq + jnp.arange(bq)[:, None]
            cols = jnp.arange(sk)[None, :]
            sblk = jnp.where((rows >= cols)[None, None, None], sblk,
                             NEG_INF)
        p = jax.nn.softmax(sblk, axis=-1)
        ob = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
        return None, ob

    _, ys = jax.lax.scan(body, None, (qf, jnp.arange(nq)))
    out = ys.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, s, d)
    return out.astype(q.dtype)


def attention(q, k, v, *, causal: bool = True, impl: str = "chunked",
              block_q: int = 128, block_k: int = 128,
              interpret: bool = False):
    if impl == "qchunk":
        return attention_qchunk(q, k, v, causal=causal,
                                block_q=max(block_q, 512))
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)
    if impl == "chunked":
        return attention_chunked(q, k, v, causal=causal,
                                 block_k=max(block_k, 512))
    if impl == "xla":
        return attention_ref(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl: {impl}")
