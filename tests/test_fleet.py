"""Fleet-layer tests: tenant interleaving, heterogeneous padding, search.

Covers the three guarantees the fleet layer is built on:

1. the tenant plumbing is free: a 1-tenant x 1-device (parity-off)
   fleet program is bit-identical to the plain ``run_program`` path;
2. heterogeneous-geometry padding is exact: a lane run under a
   ``DynConfig`` effective capacity on the padded static config leaves
   the same element-level state as an engine built with the smaller
   geometry outright, and batching lanes never changes per-device
   metrics vs independent runs;
3. the allocator search is deterministic under a fixed seed, and the
   batched engine path agrees with a real per-op ``ZNSArray`` replay.
"""

import numpy as np
import pytest

from repro.core import engine as E
from repro.core import workloads
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.fleet import (FleetConfig, N_TENANTS, build_fleet_batch,
                         evaluate_configs, grid_space, interleave_tenants,
                         pad_programs, pareto_front, random_space,
                         run_configs_legacy, run_fleet, score_rows,
                         stripe_program, tag_tenant)
from repro.fleet import runner


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1,
                         blocks_per_lun=16, pages_per_block=4,
                         page_bytes=4096)


def tiny_engine(spec=SUPERBLOCK, n_segments=4, max_active=6):
    flash = tiny_flash()
    return E.ZoneEngine(flash, ZoneGeometry(4, n_segments), spec,
                        max_active=max_active)


def churn_program(n_zones=3, cycles=2, base_pages=3):
    rows = []
    for cyc in range(cycles):
        for z in range(n_zones):
            rows.append((E.OP_WRITE, z, base_pages + 2 * z + cyc,
                         E.F_HOST))
            rows.append((E.OP_FINISH, z, 0, 0))
        for z in range(n_zones):
            rows.append((E.OP_RESET, z, 0, 0))
    return E.encode_program(rows)


def assert_states_equal(a, b, n, ctx=""):
    for name in ("elem_wear", "elem_avail", "elem_pages", "elem_zone"):
        assert np.array_equal(np.asarray(getattr(a, name)[:n]),
                              np.asarray(getattr(b, name)[:n])), \
            f"{name} {ctx}"
    for name in ("host_pages", "dummy_pages", "block_erases", "n_active"):
        assert int(getattr(a, name)) == int(getattr(b, name)), \
            f"{name} {ctx}"


# --------------------------------------------------------------------- #
# 1. tenant plumbing is bit-free on the degenerate fleet
# --------------------------------------------------------------------- #
def test_single_tenant_single_device_bit_identical():
    eng = tiny_engine()
    plain = churn_program()
    tagged = tag_tenant(plain, 0)
    merged = interleave_tenants([tagged])
    assert np.array_equal(merged, tagged)
    striped = stripe_program(merged, n_devices=1, chunk_pages=4,
                             parity=False,
                             member_zone_pages=eng.cfg.zone_pages,
                             parity_tenant=1)
    assert len(striped) == 1
    # width-4 plain scan vs width-5 fleet lane: identical final state
    s_plain, _ = eng.run(eng.init_state(), plain)
    res = run_fleet(eng, pad_programs(striped), n_tenants=1)
    runner.assert_all_ok(res)
    n = eng.cfg.n_elements
    lane = type(s_plain)(*[leaf[0] for leaf in res.states])
    assert_states_equal(s_plain, lane, n, "1x1 fleet")
    # chunked writes re-concatenate to the original host page counts
    assert int(res.host_delta.sum()) == int(s_plain.host_pages)


def test_repeated_finish_emits_parity_once():
    """FINISH on a FULL superzone is a no-op in ZNSArray; the
    program-space striper must not re-emit the partial-stripe parity
    chunk on a repeated FINISH (regression: the duplicate write was
    illegal on the FULL member zone)."""
    eng = tiny_engine()
    prog = tag_tenant(E.encode_program([
        (E.OP_WRITE, 0, 6, E.F_HOST),
        (E.OP_FINISH, 0, 0, 0),
        (E.OP_FINISH, 0, 0, 0),
    ]), 0)
    striped = stripe_program(prog, n_devices=3, chunk_pages=4,
                             parity=True,
                             member_zone_pages=eng.cfg.zone_pages,
                             parity_tenant=1)
    parity_writes = sum(
        1 for dev in striped for row in dev
        if row[0] == E.OP_WRITE and row[4] == 1)
    assert parity_writes == 1
    res = run_fleet(eng, pad_programs(striped), n_tenants=1)
    runner.assert_all_ok(res)


def test_interleave_round_robin_order():
    a = tag_tenant(E.encode_program([(E.OP_WRITE, 0, 1, 1)] * 3), 0)
    b = tag_tenant(E.encode_program([(E.OP_WRITE, 1, 1, 1)] * 2), 1)
    merged = interleave_tenants([a, b])
    assert merged[:, 4].tolist() == [0, 1, 0, 1, 0]


# --------------------------------------------------------------------- #
# 2. heterogeneous-geometry padding is exact
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("spec", [SUPERBLOCK, BLOCK, vchunk(2)],
                         ids=lambda s: s.name)
def test_hetero_padding_matches_exact_geometry(spec):
    big = tiny_engine(spec, n_segments=4)
    small = tiny_engine(spec, n_segments=2)
    assert big.cfg.n_elements == small.cfg.n_elements
    prog = churn_program()
    s_exact, _ = small.run(small.init_state(), prog)
    # the smaller geometry has MORE zones (8) than the padded static
    # table holds (4); only the shared prefix is addressable, and an
    # n_zones override past the static table now raises (it used to
    # silently index past the padded zone tables)
    s_pad, _ = big.run(
        big.init_state(), prog,
        big.dyn(zone_pages=small.cfg.zone_pages,
                n_zones=min(small.cfg.n_zones, big.cfg.n_zones)))
    assert_states_equal(s_exact, s_pad, big.cfg.n_elements,
                        f"padded {spec.name}")


@pytest.mark.parametrize("spec", [SUPERBLOCK, BLOCK, vchunk(2)],
                         ids=lambda s: s.name)
def test_hetero_padding_first_fit_matches_exact_geometry(spec):
    """First-fit under a capacity override claims the lowest free
    *columns*, as the smaller device does -- not the least-worn of the
    padded static take (regression: worn-out churn made the two pick
    different elements)."""
    small = E.ZoneEngine(tiny_flash(), ZoneGeometry(4, 2), spec,
                         max_active=6, wear_aware=False)
    big = tiny_engine(spec, n_segments=4)
    prog = churn_program(n_zones=4, cycles=4)
    s_exact, _ = small.run(small.init_state(), prog)
    s_pad, _ = big.run(
        big.init_state(), prog,
        big.dyn(zone_pages=small.cfg.zone_pages, wear_aware=False,
                n_zones=min(small.cfg.n_zones, big.cfg.n_zones)))
    assert_states_equal(s_exact, s_pad, big.cfg.n_elements,
                        f"padded first-fit {spec.name}")


def test_hetero_batch_matches_independent_runs():
    """A mixed-geometry batched dispatch must leave every lane exactly
    as its independent (unbatched) run would."""
    big = tiny_engine(SUPERBLOCK, n_segments=4)
    small = tiny_engine(SUPERBLOCK, n_segments=2)
    prog = churn_program()
    dyn = E.stack_dyn([
        big.dyn(),
        big.dyn(zone_pages=small.cfg.zone_pages),
        big.dyn(wear_aware=False),
    ])
    states, _ = big.run_batch(big.init_state(),
                              np.stack([prog, prog, prog]), dyn)
    singles = [
        big.run(big.init_state(), prog)[0],
        big.run(big.init_state(), prog,
                big.dyn(zone_pages=small.cfg.zone_pages))[0],
        big.run(big.init_state(), prog, big.dyn(wear_aware=False))[0],
    ]
    n = big.cfg.n_elements
    for k, ref in enumerate(singles):
        lane = type(ref)(*[leaf[k] for leaf in states])
        assert_states_equal(ref, lane, n, f"lane {k}")


def test_shrunk_alloc_never_steals_in_use_elements():
    """A group whose free count is in [take_eff, take) is feasible for
    a capacity-shrunk lane, but the claimed prefix must be the *free*
    elements -- the non-free top_k filler must never be reordered ahead
    of them (regression: elements VALID in another zone were stolen).

    The short-group state is built surgically: legal single-device
    programs keep per-group free counts at or above ``take`` whenever
    an EMPTY zone exists (zones tile the element set), but the engine
    must stay safe for any state a batched lane can reach."""
    import jax.numpy as jnp
    from repro.core.alloc_exact import AVAIL_ALLOCATED, AVAIL_VALID

    eng = tiny_engine(SUPERBLOCK, n_segments=4, max_active=8)
    half = eng.dyn(zone_pages=eng.cfg.zone_pages // 2)  # take_eff = 2
    s = eng.init_state()
    # elements 0..13 in use by other zones; only 14, 15 free
    avail = np.full(17, AVAIL_VALID, np.int32)
    avail[1::2] = AVAIL_ALLOCATED
    avail[14:] = 0  # FREE (incl. scratch)
    zone_of = np.repeat(np.arange(4, dtype=np.int32), 4)
    s = s._replace(
        elem_avail=jnp.asarray(avail),
        elem_zone=jnp.asarray(np.r_[zone_of[:14], -1, -1, -1]))
    avail_before = avail.copy()
    s, tr = eng.apply(s, (E.OP_WRITE, 3, 1, E.F_HOST), half)
    assert bool(tr.ok)
    claimed = np.asarray(s.zone_elems[3])
    assert sorted(int(e) for e in claimed if e >= 0) == [14, 15]
    # nothing belonging to other zones was touched
    assert np.array_equal(np.asarray(s.elem_avail[:14]),
                          avail_before[:14])
    assert np.array_equal(np.asarray(s.elem_zone[:14]), zone_of[:14])


def test_dyn_wear_aware_matches_static_engine():
    eng_ff = tiny_engine(BLOCK)
    eng = E.ZoneEngine(tiny_flash(), ZoneGeometry(4, 4), BLOCK,
                       max_active=6, wear_aware=False)
    prog = churn_program()
    s_static, _ = eng.run(eng.init_state(), prog)
    s_dyn, _ = eng_ff.run(eng_ff.init_state(), prog,
                          eng_ff.dyn(wear_aware=False))
    assert_states_equal(s_static, s_dyn, eng.cfg.n_elements, "ff dyn")


# --------------------------------------------------------------------- #
# 3. search: determinism + agreement with the per-op array replay
# --------------------------------------------------------------------- #
AXES = dict(segments=(4, 2), chunks=(8, 16))


def test_random_space_deterministic():
    a = random_space(7, 8, **AXES)
    b = random_space(7, 8, **AXES)
    assert a == b
    c = random_space(8, 8, **AXES)
    assert a != c  # a different seed explores differently


def test_search_objective_deterministic():
    eng = tiny_engine(SUPERBLOCK, n_segments=4, max_active=6)
    configs = random_space(3, 6, **AXES)
    rows1 = score_rows(evaluate_configs(eng, configs, n_devices=3))
    rows2 = score_rows(evaluate_configs(eng, configs, n_devices=3))
    assert [r["config"] for r in rows1] == [r["config"] for r in rows2]
    for r1, r2 in zip(rows1, rows2):
        assert r1 == r2
    front = pareto_front(rows1)
    assert 1 <= len(front) <= len(rows1)
    # front members are flagged, non-members dominated
    for r in rows1:
        assert r["pareto"] in (0.0, 1.0)
    assert all(r["pareto"] == 1.0 for r in front)


def test_grid_space_covers_cross_product():
    configs = grid_space(**AXES)
    assert len(configs) == len(set(configs)) == 2 * 2 * 2 * 2 * 2


def test_engine_path_matches_legacy_array_replay():
    """The batched engine fleet (padded geometry, program-space parity)
    must report the same array-level traffic as a real ZNSArray over
    per-op legacy devices built with each config's true geometry."""
    flash = tiny_flash()
    eng = E.ZoneEngine(flash, ZoneGeometry(4, 4), SUPERBLOCK,
                       max_active=6)
    configs = [FleetConfig("dlwa_pair", 4, 8, True, True),
               FleetConfig("dlwa_write", 2, 16, False, True),
               FleetConfig("dlwa_pair", 2, 8, True, False)]
    programs, dyn, merged = build_fleet_batch(eng, configs, n_devices=3)
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS)
    runner.assert_all_ok(res)
    legacy = run_configs_legacy(flash, SUPERBLOCK, configs, merged,
                                parallelism=4, n_devices=3,
                                max_active=6)
    for k, (fc, rep) in enumerate(zip(configs, legacy)):
        lanes = np.arange(3 * k, 3 * (k + 1))
        mine = runner.config_report(res, eng, lanes)
        assert mine["host_pages"] + mine["parity_pages"] == \
            rep["host_pages"] + rep["parity_pages"], fc
        assert mine["parity_pages"] == rep["parity_pages"], fc
        assert mine["dummy_pages"] == rep["dummy_pages"], fc
        assert mine["dlwa"] == pytest.approx(rep["dlwa"]), fc
        assert mine["block_erases"] == rep["total_block_erases"], fc
        assert mine["wear_cv"] == pytest.approx(rep["wear_cv"]), fc


def test_fleet_timing_sane():
    eng = tiny_engine()
    prog = tag_tenant(workloads.dlwa_program(eng, occupancy=0.5,
                                             n_zones=2), 0)
    res = run_fleet(eng, pad_programs([prog, prog]), n_tenants=1)
    active = res.pages > 0
    assert (res.completions[active] > 0).all()
    assert (res.latencies[active] > 0).all()
    # NOP / zero-page ops contribute nothing
    assert (res.completions[~active] == 0).all()
    assert np.allclose(res.makespans, res.completions.max(axis=1))
    p99 = res.tenant_p99_latency(np.arange(2))
    assert p99[0] > 0
