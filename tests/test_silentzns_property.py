"""Property layer for the SilentZNS on-the-fly allocation policy.

The ``alloc_policy="silent"`` axis commits a zone's block collection on
the fly instead of pinning the whole static grid at ALLOC.  Three
invariant families are fuzzed here with hypothesis:

1. every claim -- initial ALLOC and on-demand growth alike -- respects
   the wear-leveling bound (no claimed block more than ``wear_bound``
   erases above the freshest free block at claim time) and the
   parallelism floor (an open zone's collection spans exactly
   ``zone_groups`` distinct LUN groups, one rank at a time);
2. no block is double-claimed: the per-zone element tables stay
   disjoint and consistent with the reverse ``elem_zone`` map;
3. ``alloc_policy="traditional"`` is bit-identical to the existing
   allocator on all five element specs (the policy axis must be a pure
   extension), and fill+FINISH page accounting (host, dummy, DLWA) is
   policy-independent -- only wear/erase traffic may diverge.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core.device_legacy import LegacyZNSDevice
from repro.core.elements import (BLOCK, FIXED, SUPERBLOCK, hchunk, vchunk)
from repro.core.geometry import FlashGeometry, ZoneGeometry

SPECS = [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK, FIXED]


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=8,
                         pages_per_block=4, page_bytes=4096)


def tiny_engine(spec, max_active=3):
    return E.ZoneEngine(tiny_flash(), ZoneGeometry(4, 2), spec,
                        max_active=max_active)


#: one fuzz op row: (opcode, zone, n_pages, host).  Explicit ALLOC rows
#: exercise the hint-sized initial claim; WRITE past the commitment
#: exercises on-demand growth; n_pages past the 32-page zone mixes in
#: illegal overflow writes.
_ROW = st.tuples(
    st.sampled_from([E.OP_ALLOC, E.OP_WRITE, E.OP_FINISH, E.OP_RESET]),
    st.integers(0, 3),
    st.integers(1, 34),
    st.booleans(),
)


# --------------------------------------------------------------------- #
# 1 + 2. claim invariants under fuzzed churn, op by op
# --------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(st.lists(_ROW, min_size=1, max_size=24),
       st.sampled_from([None, 0, 1, 3]))
def test_silent_claims_respect_bounds_and_stay_disjoint(rows, wear_bound):
    """Every silent-policy claim is wear-bounded and rank-rectangular
    across the parallelism groups, and the zone element tables never
    share a block.  Checked after every op so the invariant holds at
    claim time, not just at the end."""
    eng = tiny_engine(BLOCK)
    dyn = eng.dyn(alloc_policy="silent", wear_bound=wear_bound)
    cfg, n = eng.cfg, eng.cfg.n_elements
    zg = int(dyn.zone_groups)
    bound = float("inf") if wear_bound is None else wear_bound
    groups = np.arange(n) // cfg.per_group
    state = eng.init_state()
    for i, (op, z, pages, host) in enumerate(rows):
        pre_zone = np.asarray(state.elem_zone)[:n].copy()
        pre_wear = np.asarray(state.elem_wear)[:n].copy()
        pre_avail = np.asarray(state.elem_avail)[:n].copy()
        prog = E.encode_program([(op, z, pages,
                                  E.F_HOST if host else 0)])
        state, _ = eng.run(state, prog, dyn)
        post_zone = np.asarray(state.elem_zone)[:n]
        ctx = f"i={i} row={rows[i]} wear_bound={wear_bound}"
        # wear bound: a block claimed this op was within `bound` erases
        # of the freshest free block available before the op
        new = (pre_zone < 0) & (post_zone >= 0)
        if new.any():
            free = ((pre_avail == E.AVAIL_FREE)
                    | (pre_avail == E.AVAIL_INVALID))
            assert free.any(), ctx
            slack = pre_wear[new] - pre_wear[free].min()
            assert (slack <= bound).all(), f"wear slack {slack} {ctx}"
        # parallelism floor: an OPEN zone's collection spans exactly
        # zone_groups distinct LUN groups, in whole ranks (FINISH may
        # later free untouched blocks, so FULL zones are exempt)
        zstates = np.asarray(state.zone_state)
        for zz in range(cfg.n_zones):
            mine = post_zone == zz
            if mine.any() and zstates[zz] == E.ZONE_OPEN:
                got = set(groups[mine].tolist())
                assert len(got) == zg, f"zone {zz} groups {got} {ctx}"
                assert int(mine.sum()) % zg == 0, f"zone {zz} {ctx}"
        # no double claim: zone tables disjoint + reverse-map consistent
        ze = np.asarray(state.zone_elems)
        owner = {}
        for zz in range(cfg.n_zones):
            for e in ze[zz][ze[zz] >= 0].tolist():
                assert e not in owner, \
                    f"elem {e} in zones {owner.get(e)} and {zz} {ctx}"
                owner[e] = zz
                assert post_zone[e] == zz, f"elem {e} reverse map {ctx}"


@settings(max_examples=6, deadline=None)
@given(st.lists(_ROW, min_size=1, max_size=24))
def test_silent_growth_equals_one_shot_commitment(rows):
    """Replaying the same program must be deterministic, and a zone
    grown across several WRITEs must end with the same collection shape
    (group span, rank multiple) as the claim invariants demand -- the
    growth path shares `_take_lowest` with ALLOC, so a divergence here
    is a growth-bookkeeping bug."""
    eng = tiny_engine(BLOCK)
    dyn = eng.dyn(alloc_policy="silent")
    prog = E.encode_program([(op, z, n, E.F_HOST if host else 0)
                             for op, z, n, host in rows])
    s1, t1 = eng.run(eng.init_state(), prog, dyn)
    s2, t2 = eng.run(eng.init_state(), prog, dyn)
    for a, b in zip(s1, s2):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(t1.ok), np.asarray(t2.ok))


# --------------------------------------------------------------------- #
# 3. the policy axis is a pure extension
# --------------------------------------------------------------------- #
@settings(max_examples=6, deadline=None)
@given(st.integers(0, len(SPECS) - 1), st.integers(1, 4),
       st.lists(_ROW, min_size=1, max_size=30))
def test_traditional_policy_bit_identical(spec_i, max_active, rows):
    """`alloc_policy="traditional"` must leave the exact pytree the
    default dyn leaves on every element spec, and both must replay the
    legacy per-op device exactly -- the new axis cannot perturb the
    existing allocator by even one bit."""
    spec = SPECS[spec_i]
    eng = tiny_engine(spec, max_active=max_active)
    # OP_ALLOC has no legacy per-op equivalent in this oracle loop;
    # keep the op mix to the legacy surface
    rows = [(E.OP_WRITE if op == E.OP_ALLOC else op, z, n, host)
            for op, z, n, host in rows]
    prog = E.encode_program([(op, z, n, E.F_HOST if host else 0)
                             for op, z, n, host in rows])
    base_state, base_trace = eng.run(eng.init_state(), prog)
    trad_state, trad_trace = eng.run(eng.init_state(), prog,
                                     eng.dyn(alloc_policy="traditional"))
    ctx = f"spec={spec.name} ma={max_active}"
    for mine, ref in zip(trad_state, base_state):
        assert np.array_equal(np.asarray(mine), np.asarray(ref)), ctx
    assert np.array_equal(np.asarray(trad_trace.ok),
                          np.asarray(base_trace.ok)), ctx
    # and the pre-policy-axis oracle: the legacy stateful device
    leg = LegacyZNSDevice(tiny_flash(), ZoneGeometry(4, 2), spec,
                          max_active=max_active)
    for op, z, n, host in rows:
        try:
            if op == E.OP_WRITE:
                leg.zone_write(z, n, host=host)
            elif op == E.OP_FINISH:
                leg.zone_finish(z)
            else:
                leg.zone_reset(z)
        except RuntimeError:
            pass
    ne = eng.cfg.n_elements
    assert np.array_equal(np.asarray(trad_state.elem_wear[:ne]),
                          leg.elem_wear), ctx
    assert np.array_equal(np.asarray(trad_state.elem_avail[:ne]),
                          leg.elem_avail), ctx
    assert np.array_equal(np.asarray(trad_state.elem_pages[:ne]),
                          leg.elem_pages), ctx
    assert np.array_equal(np.asarray(trad_state.elem_zone[:ne]),
                          leg.elem_zone), ctx
    assert int(trad_state.host_pages) == leg.host_pages, ctx
    assert int(trad_state.dummy_pages) == leg.dummy_pages, ctx
    assert int(trad_state.block_erases) == leg.block_erases, ctx
    assert int(trad_state.n_active) == leg.n_active, ctx


@settings(max_examples=8, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 32)),
                min_size=1, max_size=10))
def test_fill_finish_page_accounting_is_policy_independent(fills):
    """Host/dummy page totals (hence DLWA) of fill+FINISH traffic are a
    function of the write pointers alone -- the silent policy changes
    *which* blocks hold the pages, never how many pages FINISH pads.
    This is the identity the paper-headline differential oracle relies
    on (see ``tests/test_engine_diff.py``)."""
    eng = tiny_engine(BLOCK, max_active=4)
    rows = [(E.OP_WRITE, z, n, E.F_HOST) for z, n in fills]
    rows += [(E.OP_FINISH, z, 0, 0) for z in range(4)]
    prog = E.encode_program(rows)
    out = {}
    for policy in ("traditional", "silent"):
        state, trace = eng.run(eng.init_state(), prog,
                               eng.dyn(alloc_policy=policy))
        out[policy] = (int(state.host_pages), int(state.dummy_pages),
                       np.asarray(trace.ok).tolist())
    assert out["traditional"] == out["silent"], fills
