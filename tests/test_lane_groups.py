"""Lane groups: ``run_programs``' batched path equals its one-lane path.

On the TPU ``run_programs`` steps the lanes of a dispatch together in
lane groups (:func:`repro.core.engine._run_lane_groups`); on the CPU it
keeps them one after another (:func:`repro.core.engine._run_lanes`).
The two must agree bit for bit on every ``DeviceState`` field, every
``OpTrace`` field and the telemetry, so the batched path is reached here
through its private function and compared with the one-lane path on
the CPU: the superblock/block/vchunk2 union engine with per-lane specs,
both allocation policies, random capacity shrinks and wear bounds,
widths that do and do not divide the lane count, and lanes of one group
that allocate on different steps.
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.check import verifier
from repro.core import engine as E
from repro.core.geometry import zn540
from repro.fleet import runner
from repro.obs import Profiler
from repro.obs.recorder import ObsConfig

from test_engine_diff import _FUZZ_ROW as _DIFF_ROW
from test_union_spec import (HALF, N_OPS, UNION, UNION_SPECS, _FUZZ_ROW,
                             pad_rows)

CFG = UNION.cfg
OBS = ObsConfig(n_buckets=4, n_tenants=2)

_groups = jax.jit(E._run_lane_groups, static_argnums=(0, 4, 5))
_lanes = jax.jit(E._run_lanes, static_argnums=(0, 4))

#: one lane: (spec index, policy, halve the capacity?, wear-aware?,
#: wear bound or None, rows).  Each lane gets rows of its own, so lanes
#: of one group open zones on different steps; explicit ALLOCs with a
#: size hint (the silent policy's commitment) join the union fuzz rows,
#: and the engine-diff rows (shorter writes) make zones fill in steps.
_ALLOC_ROW = st.tuples(st.just(E.OP_ALLOC), st.integers(0, 3),
                       st.integers(0, 40), st.just(True))
_LANE = st.tuples(
    st.integers(0, len(UNION_SPECS) - 1), st.sampled_from(
        ["traditional", "silent"]), st.booleans(), st.booleans(),
    st.one_of(st.none(), st.integers(0, 3)),
    st.lists(st.one_of(_FUZZ_ROW, _DIFF_ROW, _ALLOC_ROW), min_size=0,
             max_size=N_OPS))


def _dyn(spec_i, policy, shrink, wear, bound):
    kw = dict(spec=UNION_SPECS[spec_i], alloc_policy=policy,
              wear_aware=wear, wear_bound=bound)
    if shrink:
        kw["zone_pages"] = HALF
    return UNION.dyn(**kw)


def _batch(lanes):
    progs = np.stack([pad_rows([(op, z, n, E.F_HOST if host else 0)
                                for op, z, n, host in rows])
                      for *_, rows in lanes])
    # column 4: a tenant tag for the telemetry's per-tenant axis
    tags = np.broadcast_to((np.arange(N_OPS) % 2)[None, :, None],
                           progs.shape[:2] + (1,))
    progs = np.concatenate([progs, tags.astype(np.int32)], axis=2)
    return progs, E.stack_dyn([_dyn(*lane[:5]) for lane in lanes])


def _assert_same(got, want):
    for part, a, b in zip(("state", "trace", "telemetry"), got, want):
        for name in a._fields:
            x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
            assert x.dtype == y.dtype and x.shape == y.shape, (part, name)
            assert np.array_equal(x, y), f"{part}.{name} differs"


def _check(lanes, width, obs):
    progs, dyn = _batch(lanes)
    state = UNION.init_state()
    got = _groups(CFG, state, progs, dyn, obs, width)
    want = _lanes(CFG, state, progs, dyn, obs)
    assert len(got) == (3 if obs else 2)
    _assert_same(got, want)


@pytest.mark.parametrize("obs", [None, OBS], ids=["plain", "obs"])
@pytest.mark.parametrize("width,n_lanes", [(2, 4), (3, 7)],
                         ids=["w2x4", "w3x7"])
@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_lane_groups_equal_lanes_one_after_another(width, n_lanes, obs,
                                                    data):
    """Fuzzed programs, one per lane, each under its own spec, policy,
    capacity, allocator and wear bound: the lane-group path (width 2 on
    4 lanes, and width 3 on 7 lanes, filled up with NOP lanes) leaves
    every state, trace and telemetry field equal to the one-lane path."""
    lanes = data.draw(st.lists(_LANE, min_size=n_lanes, max_size=n_lanes))
    _check(lanes, width, obs)


@pytest.mark.parametrize("obs", [None, OBS], ids=["plain", "obs"])
def test_lane_groups_allocate_on_different_steps(obs):
    """A group whose lanes open zones on different steps (lane 0 at
    step 0, lane 1 at step 3, lane 2 at step 5 and by a WRITE to an
    EMPTY zone), with steps in between where no lane of the group
    allocates, and silent lanes whose writes outgrow their committed
    ranks; width 2 over 3 lanes, so one group holds a filler lane."""
    zp = E.make_dyn(CFG).zone_pages
    q = int(zp) // 8
    rows = [
        [(E.OP_ALLOC, 0, q, True), (E.OP_WRITE, 0, q, True),
         (E.OP_WRITE, 0, q, True), (E.OP_WRITE, 0, 3 * q, True),
         (E.OP_FINISH, 0, 0, True), (E.OP_RESET, 0, 0, True),
         (E.OP_WRITE, 1, q, False)],
        [(E.OP_NOP, 0, 0, False)] * 3 + [
            (E.OP_ALLOC, 2, 0, True), (E.OP_WRITE, 2, 2 * q, True),
            (E.OP_WRITE, 2, 6 * q, True), (E.OP_READ, 2, 1, True)],
        [(E.OP_NOP, 0, 0, False)] * 5 + [
            (E.OP_WRITE, 3, q, True), (E.OP_WRITE, 3, 5 * q, True),
            (E.OP_FINISH, 3, 0, True)],
    ]
    lanes = [(0, "silent", False, True, None, rows[0]),
             (1, "traditional", False, True, None, rows[1]),
             (2, "silent", True, True, 1, rows[2])]
    _check(lanes, 2, obs)


@pytest.mark.parametrize("width", [1, 2, 3])
def test_lane_groups_without_dyn_use_the_primary_member(width):
    """``run_programs`` without a dyn runs every lane under ``cfg``'s
    primary member, in lane groups as one after another."""
    rows = [(E.OP_WRITE, 1, 9, E.F_HOST), (E.OP_FINISH, 1, 0, 0)]
    progs = np.stack([pad_rows(rows)] * 5)
    state = UNION.init_state()
    dyn = E._lane_dyn(CFG, None, 5)
    want = _lanes(CFG, state, progs, dyn, None)
    got = (_lanes(CFG, state, progs, dyn, None) if width == 1 else
           _groups(CFG, state, progs, dyn, None, width))
    _assert_same(got, want)
    _assert_same(E.run_programs(CFG, state, progs), want)


@pytest.mark.parametrize("n_lanes,want", [
    (1, 1), (6, 1), (32, 1), (63, 1), (64, 64), (96, 96), (384, 384),
    (385, 384), (1024, 384)])
def test_lane_group_width_rule(n_lanes, want):
    """Width 1 on the CPU at any lane count; on the TPU lanes one after
    another below 64 lanes, else every lane in one group, up to 384."""
    assert E.lane_group_width(n_lanes, "cpu") == 1
    assert E.lane_group_width(n_lanes, "tpu") == want


def _opens_by_verifier(programs, dyn):
    """(L, n_ops): an ALLOC or WRITE row meeting an EMPTY zone, from
    the rows and the zone states of the numpy verifier's model."""
    out = np.zeros(programs.shape[:2], bool)
    for k, prog in enumerate(programs):
        model = verifier._Model(CFG, verifier._Dv(E.dyn_values(CFG, dyn, k)))
        for i, row in enumerate(prog):
            zone = int(np.clip(row[1], 0, model.dv.n_zones - 1))
            out[k, i] = (row[0] in (E.OP_ALLOC, E.OP_WRITE)
                         and model.zone_state[zone] == E.ZONE_EMPTY)
            model.apply(i, row)
    return out


def test_run_fleet_counts_groups_lane_steps_and_alloc_steps(monkeypatch):
    """``run_fleet`` counts its dispatch's lane groups, lane steps
    (groups x width x rows, filler lanes included) and the group-steps
    in which some lane ran the allocator, at the width the dispatch
    runs: 1 on the CPU, and 3 when the rule says so, with the same
    results either way.  The allocator steps equal a recount from the
    rows and the verifier's zone states."""
    rows = [(E.OP_WRITE, k % 4, 5 + k, E.F_HOST) for k in range(6)] + [
        (E.OP_RESET, 1, 0, 0), (E.OP_ALLOC, 1, 3, E.F_HOST),
        (E.OP_FINISH, 2, 0, 0), (E.OP_WRITE, 1, 4, E.F_HOST)]
    # lane k idles k steps first, so the lanes open zones on different
    # steps
    progs = np.stack([np.concatenate(
        [pad_rows([(E.OP_NOP, 0, 0, 0)] * k + rows[k:k + 6])[:24],
         np.zeros((24, 1), np.int32)], axis=1) for k in range(5)])
    dyn = E.stack_dyn([UNION.dyn(spec=UNION_SPECS[k % 3],
                                 alloc_policy=("traditional", "silent")[k % 2])
                       for k in range(5)])
    opens = _opens_by_verifier(progs, dyn)
    assert (opens.any(axis=0) & ~opens.all(axis=0)).any()

    def run():
        prof = Profiler()
        with prof.section("call"):
            res = runner.run_fleet(UNION, progs, dyn=dyn)
        return res, prof.counters

    res1, c1 = run()
    assert c1["engine.groups"] == 5
    assert c1["engine.lane_steps"] == 5 * 24
    assert c1["engine.alloc_steps"] == opens.sum()
    monkeypatch.setattr(E, "lane_group_width", lambda n, platform: 3)
    E.run_programs.clear_cache()
    try:
        res3, c3 = run()
    finally:
        E.run_programs.clear_cache()
    assert c3["engine.groups"] == 2
    assert c3["engine.lane_steps"] == 2 * 3 * 24
    grouped = opens[:3].any(axis=0).sum() + opens[3:].any(axis=0).sum()
    assert c3["engine.alloc_steps"] == grouped < opens.sum()
    for name in ("ok", "host_delta", "dummy_delta", "erase_delta", "pages",
                 "cols", "completions", "latencies", "makespans"):
        assert np.array_equal(getattr(res1, name), getattr(res3, name)), name
    for a, b in zip(res1.states, res3.states):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("geometry", ["tiny", "zn540"])
def test_dense_selections_equal_top_k_forms(geometry):
    """The sort- and gather-free forms lane groups run in place of the
    allocator's ``top_k`` selections give the same bits on random
    group grids: wear with many ties, every availability state, groups
    out of the window, both allocators, every claim count."""
    if geometry == "tiny":
        cfg = CFG
    else:
        flash, zone = zn540()
        cfg = E.ZoneEngine(flash, zone, UNION_SPECS).cfg
    members = [s for s, _ in cfg.members]
    take = jax.jit(E._take_lowest.__wrapped__, static_argnums=0)
    take_dense = jax.jit(E._take_lowest_dense, static_argnums=0)
    wear = jax.jit(E._smallest_wear.__wrapped__, static_argnums=0)
    wear_dense = jax.jit(E._smallest_wear_dense, static_argnums=0)
    rng = np.random.default_rng(16)
    shape = (cfg.n_groups, cfg.per_group)
    for t in range(24):
        w2 = rng.integers(0, (3, 40, 5000)[t % 3], shape).astype(np.int32)
        a2 = rng.choice(4, shape, p=((.25,) * 4, (.7, .1, .1, .1),
                                     (.02, .96, .01, .01))[t % 3])
        a2 = a2.astype(np.int32)
        elig = rng.random(cfg.n_groups) < 0.7
        args = (E.make_dyn(cfg, spec=members[t % len(members)]), w2, a2,
                elig, np.bool_(t % 2),
                np.int32(rng.integers(1, cfg.take + 1)))
        for a, b in zip(take(cfg, *args), take_dense(cfg, *args)):
            assert np.array_equal(a, b), t
        ok = rng.random(shape) < (0.5, 0.01, 0.99)[t % 3]
        assert np.array_equal(wear(cfg, w2, ok), wear_dense(cfg, w2, ok)), t
