"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core.elements import BLOCK, SUPERBLOCK, hchunk, vchunk
from repro.core.engine import ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.kernels.zns_alloc.ops import zns_alloc
from repro.kernels.zns_alloc.ref import zns_alloc_ref
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.ssm_scan.ops import ssm_scan, single_step
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def tol(dtype):
    return 2.5e-2 if dtype == jnp.bfloat16 else 5e-5


# --------------------------------------------------------------------- #
# zns_alloc
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("g,w,take", [(2, 8, 1), (4, 64, 4), (8, 128, 3),
                                      (16, 256, 8), (3, 33, 5)])
def test_zns_alloc_matches_ref(g, w, take):
    rng = np.random.default_rng(g * 1000 + w + take)
    wear = jnp.asarray(rng.integers(0, 99, (g, w)), jnp.int32)
    avail = jnp.asarray(rng.choice([0, 1, 2, 3], (g, w)), jnp.int32)
    elig = jnp.asarray(rng.random(g) < 0.8)
    s_pal, f_pal = zns_alloc(wear, avail, elig, take=take, impl="pallas",
                             interpret=True)
    s_ref, ok = zns_alloc_ref(wear, avail, elig, take=take)
    assert (np.asarray(s_pal) == np.asarray(s_ref, bool)).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_zns_alloc_matches_exact_dp(seed):
    """Kernel vs the ILP dynamic program on balanced instances."""
    from repro.core import alloc_exact
    rng = np.random.default_rng(seed)
    g, w, take = 4, 16, 3
    wear = rng.integers(0, 50, (g, w)).astype(np.int32)
    avail = rng.choice([0, 1, 2, 3], (g, w)).astype(np.int32)
    elig_idx = list(range(g))
    sel, feas = zns_alloc(jnp.asarray(wear), jnp.asarray(avail),
                          jnp.ones(g, bool), take=take, impl="pallas",
                          interpret=True)
    dp = alloc_exact.solve(wear.reshape(-1), avail.reshape(-1),
                           np.repeat(np.arange(g), w), z=take * g,
                           k_max=take, l_min=g, eligible_groups=elig_idx)
    assert bool(feas) == dp.feasible
    if dp.feasible:
        assert float(wear[np.asarray(sel)].sum()) == pytest.approx(dp.cost)


_ALLOC_SPECS = [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK]


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("spec", _ALLOC_SPECS, ids=lambda s: s.name)
def test_zns_alloc_matches_engine_claim(spec, seed):
    """The kernel's per-group lowest-(wear, col) selection is exactly
    the element set a wear-aware traditional ALLOC claims for a fresh
    zone, and its feasibility flag is exactly the op's ok verdict."""
    eng = ZoneEngine(FlashGeometry(4, 1, 8, 4, 4096), ZoneGeometry(4, 2),
                     spec, max_active=3, wear_aware=True)
    cfg = eng.cfg
    # with the round-robin window spanning every group, the rr pass and
    # its cheapest-groups fallback see the same eligibility, so kernel
    # feasibility on the all-groups mask is exactly the engine's; a
    # full-capacity zone also claims all `take` ranks per group
    assert cfg.zone_groups == cfg.n_groups
    assert cfg.n_slots == cfg.take * cfg.zone_groups

    rng = np.random.default_rng(1234 * (seed + 1) + cfg.n_elements)
    n = cfg.n_elements
    wear = np.zeros(n + 1, np.int32)
    wear[:n] = rng.integers(0, 50, n)
    avail = np.zeros(n + 1, np.int32)
    avail[:n] = rng.choice([0, 1, 2, 3], n)
    state = eng.init_state()._replace(
        elem_wear=jnp.asarray(wear), elem_avail=jnp.asarray(avail))

    prog = np.asarray([[E.OP_ALLOC, 0, 0, 0]], np.int32)
    after, trace = eng.run(state, prog)

    wear2d = wear[:n].reshape(cfg.n_groups, cfg.per_group)
    avail2d = avail[:n].reshape(cfg.n_groups, cfg.per_group)
    sel, feas = zns_alloc(jnp.asarray(wear2d), jnp.asarray(avail2d),
                          jnp.ones(cfg.n_groups, bool), take=cfg.take,
                          impl="pallas", interpret=True)
    assert bool(trace.ok[0]) == bool(feas)
    if bool(feas):
        g, c = np.nonzero(np.asarray(sel, bool))
        kernel_ids = set((g * cfg.per_group + c).tolist())
        row = np.asarray(after.zone_elems)[0]
        engine_ids = {int(e) for e in row if e >= 0}
        assert engine_ids == kernel_ids


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b,hq,hkv,s,d,causal", [
    (2, 4, 2, 64, 32, True),
    (1, 8, 8, 128, 64, True),    # MHA
    (2, 8, 1, 96, 16, True),     # MQA
    (1, 4, 2, 64, 128, False),   # bidirectional
])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, dtype):
    rng = np.random.default_rng(b + hq + s + d)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    ref = attention_ref(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal, impl="pallas",
                    block_q=32, block_k=32, interpret=True)
    assert rel_err(out, ref) < tol(dtype)
    out2 = attention(q, k, v, causal=causal, impl="chunked", block_k=32)
    assert rel_err(out2, ref) < tol(dtype)


def test_flash_attention_block_shape_invariance():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    outs = [attention(q, k, v, impl="pallas", block_q=bq, block_k=bk,
                      interpret=True)
            for bq, bk in ((128, 128), (64, 32), (32, 64), (16, 16))]
    for o in outs[1:]:
        assert rel_err(o, outs[0]) < 1e-5


# --------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 8, 2, 256, 32),
    (1, 4, 4, 128, 64),
    (3, 8, 1, 64, 16),
    (1, 16, 2, 512, 128),
])
def test_decode_attention_sweep(b, hq, hkv, s, d, dtype):
    rng = np.random.default_rng(b * 31 + s)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    lengths = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    ref = decode_attention_ref(q, k, v, lengths)
    out = decode_attention(q, k, v, lengths, impl="pallas", block_s=64,
                           interpret=True)
    assert rel_err(out, ref) < tol(dtype)
    out2 = decode_attention(q, k, v, lengths, impl="chunked")
    assert rel_err(out2, ref) < tol(dtype)


def test_decode_attention_respects_length():
    """Tokens beyond `length` must not influence the output."""
    rng = np.random.default_rng(5)
    b, hq, hkv, s, d = 1, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    lengths = jnp.asarray([40], jnp.int32)
    out1 = decode_attention(q, k, v, lengths, impl="pallas", block_s=32,
                            interpret=True)
    k2 = k.at[:, 40:].set(999.0)
    v2 = v.at[:, 40:].set(-999.0)
    out2 = decode_attention(q, k2, v2, lengths, impl="pallas", block_s=32,
                            interpret=True)
    assert rel_err(out1, out2) < 1e-6


# --------------------------------------------------------------------- #
# ssm scan
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("bh,t,p,n,chunk", [
    (2, 64, 16, 8, 16),
    (1, 128, 32, 16, 64),
    (4, 32, 8, 4, 32),
])
def test_ssm_scan_sweep(bh, t, p, n, chunk, dtype):
    rng = np.random.default_rng(bh + t + p)
    x = jnp.asarray(rng.standard_normal((bh, t, p)) * 0.5, dtype)
    dt = jnp.asarray(rng.random((bh, t, p)) * 0.1 + 0.01, dtype)
    b = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, dtype)
    c = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, dtype)
    a = jnp.asarray(-np.abs(rng.standard_normal((p, n))) - 0.1, jnp.float32)
    d = jnp.asarray(rng.standard_normal(p) * 0.1, jnp.float32)
    ref = ssm_scan_ref(x, dt, b, c, a, d)
    out = ssm_scan(x, dt, b, c, a, d, impl="pallas", chunk=chunk,
                   interpret=True)
    assert rel_err(out, ref) < tol(dtype)


def test_ssm_single_step_consistent_with_scan():
    rng = np.random.default_rng(9)
    bh, t, p, n = 2, 16, 8, 4
    x = jnp.asarray(rng.standard_normal((bh, t, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.random((bh, t, p)) * 0.1 + 0.01, jnp.float32)
    b = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, jnp.float32)
    c = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((p, n))) - 0.1, jnp.float32)
    d = jnp.asarray(rng.standard_normal(p) * 0.1, jnp.float32)
    ref = ssm_scan_ref(x, dt, b, c, a, d)
    h = jnp.zeros((bh, p, n), jnp.float32)
    for i in range(t):
        h, y = single_step(h, x[:, i], dt[:, i], b[:, i], c[:, i], a, d)
        assert rel_err(y, ref[:, i]) < 1e-5


def test_ssm_scan_chunk_invariance():
    rng = np.random.default_rng(11)
    bh, t, p, n = 1, 64, 8, 4
    x = jnp.asarray(rng.standard_normal((bh, t, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.random((bh, t, p)) * 0.1 + 0.01, jnp.float32)
    b = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, jnp.float32)
    c = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.5, jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((p, n))) - 0.1, jnp.float32)
    d = jnp.asarray(rng.standard_normal(p) * 0.1, jnp.float32)
    outs = [ssm_scan(x, dt, b, c, a, d, impl="pallas", chunk=ch,
                     interpret=True)
            for ch in (8, 16, 32, 64)]
    for o in outs[1:]:
        assert rel_err(o, outs[0]) < 1e-6
