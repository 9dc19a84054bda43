"""Union-config tests: per-lane element specs in one fleet dispatch.

The tentpole exactness oracle for the ``DynConfig`` spec axis: a mixed
SUPERBLOCK + BLOCK + VCHUNK batch through one padded union
:class:`~repro.core.engine.EngineConfig` must be *bit-identical* per
lane to independent dispatches on engines built with each spec
outright -- element wear/avail/pages, zone tables, counters, the lot.
Programs are hypothesis-fuzzed (legal and illegal ops mixed, like
``test_engine_diff.py``'s program fuzz), and the spec axis composes
with the established capacity-shrink and allocator overrides.  The
dyn-derived slot map that replaces the static per-spec
``element_pages`` reduction is property-checked against the closed
forms for every element kind.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core import zns
from repro.core.elements import (BLOCK, FIXED, SUPERBLOCK, hchunk, vchunk)
from repro.core.geometry import FlashGeometry, ZoneGeometry

UNION_SPECS = (SUPERBLOCK, BLOCK, vchunk(2))


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1,
                         blocks_per_lun=16, pages_per_block=4,
                         page_bytes=4096)


ZGEOM = ZoneGeometry(4, 4)
FLASH = tiny_flash()
UNION = E.ZoneEngine(FLASH, ZGEOM, UNION_SPECS, max_active=6)
SINGLES = {s: E.ZoneEngine(FLASH, ZGEOM, s, max_active=6)
           for s in UNION_SPECS}
N_OPS = 32          # fixed padded program length (one compiled shape)
HALF = ZGEOM.zone_pages(FLASH) // 2


def pad_rows(rows):
    prog = np.zeros((N_OPS, 4), dtype=np.int32)
    enc = E.encode_program(rows)[:N_OPS]
    prog[: len(enc)] = enc
    return prog


def assert_lane_matches_single(states, trace, k, spec, ref, ref_trace,
                               ctx=""):
    """Union batch lane ``k`` == the single-spec engine's final state,
    with member element ids mapped onto the union grid."""
    single = SINGLES[spec]
    ids = UNION.member_element_ids(spec)
    for name in ("elem_wear", "elem_avail", "elem_pages", "elem_zone"):
        a = np.asarray(getattr(states, name)[k])[ids]
        b = np.asarray(getattr(ref, name))[: len(ids)]
        assert np.array_equal(a, b), f"{name} {ctx}"
    for name in ("host_pages", "dummy_pages", "block_erases",
                 "alloc_calls", "n_active", "rr_next"):
        assert int(getattr(states, name)[k]) == int(getattr(ref, name)), \
            f"{name} {ctx}"
    for name in ("zone_state", "zone_wp", "zone_host_wp", "zone_cols"):
        assert np.array_equal(np.asarray(getattr(states, name)[k]),
                              np.asarray(getattr(ref, name))), \
            f"{name} {ctx}"
    # zone slot tables: the lane's slots hold union ids (dense ids
    # mapped through the member grid); slots past the member's slot
    # count stay unmapped
    ns = single.cfg.n_slots
    lut = np.full(single.cfg.n_elements + 1, -1, np.int64)
    lut[: len(ids)] = ids
    ze_ref = np.asarray(ref.zone_elems)
    mapped = np.where(ze_ref >= 0, lut[np.clip(ze_ref, 0, len(ids))], -1)
    ze = np.asarray(states.zone_elems[k])
    assert np.array_equal(ze[:, :ns], mapped), f"zone_elems {ctx}"
    assert (ze[:, ns:] == -1).all(), f"zone_elems tail {ctx}"
    # per-op legality must line up too (same illegal ops rejected)
    assert np.array_equal(np.asarray(trace.ok[k]),
                          np.asarray(ref_trace.ok)), f"ok {ctx}"


# --------------------------------------------------------------------- #
# the dyn-derived slot map == the per-kind closed forms
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "spec", [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK, FIXED],
    ids=lambda s: s.name)
def test_generic_slot_map_matches_element_pages(spec):
    """``_written_per_slot`` now derives the (segment, column) -> slot
    assignment from DynConfig values; for every element kind and every
    write pointer it must reproduce ``zns.element_pages`` exactly."""
    cfg, _ = E.make_config(FLASH, ZGEOM, spec, max_active=6)
    dyn = E.make_dyn(cfg)
    for wp in range(cfg.zone_pages + 1):
        want = zns.element_pages(wp, spec, cfg.parallelism,
                                 cfg.n_segments, cfg.pages_per_block)
        got = np.asarray(E._written_per_slot(cfg, dyn, wp))
        assert np.array_equal(got[: len(want)], want), (spec.name, wp)
        assert (got[len(want):] == 0).all(), (spec.name, wp)


# --------------------------------------------------------------------- #
# union construction invariants
# --------------------------------------------------------------------- #
def test_union_config_padded_to_maxima():
    cfg = UNION.cfg
    singles = [SINGLES[s].cfg for s in UNION_SPECS]
    assert cfg.n_groups == max(c.n_groups for c in singles)
    assert cfg.per_group == max(c.per_group for c in singles)
    assert cfg.n_elements == cfg.n_groups * cfg.per_group
    assert cfg.n_slots == max(c.n_slots for c in singles)
    assert set(dict(cfg.members)) == set(UNION_SPECS)
    for s in UNION_SPECS:
        v = cfg.member_values(s)
        assert v.n_elements == SINGLES[s].cfg.n_elements
        assert v.pages_per_element == SINGLES[s].cfg.pages_per_element
    # a plain config is its own single member
    single = SINGLES[BLOCK].cfg
    assert dict(single.members).keys() == {BLOCK}
    with pytest.raises(ValueError, match="not a member"):
        single.member_values(SUPERBLOCK)


def test_union_config_rejections():
    with pytest.raises(ValueError, match="at least one spec"):
        E.make_union_config(FLASH, ZGEOM, ())
    with pytest.raises(ValueError, match="duplicate"):
        E.make_union_config(FLASH, ZGEOM, (BLOCK, BLOCK))
    with pytest.raises(ValueError, match="FIXED"):
        E.make_union_config(FLASH, ZGEOM, (BLOCK, FIXED))


# --------------------------------------------------------------------- #
# the exactness oracle: mixed-spec batch == per-spec dispatches
# --------------------------------------------------------------------- #
#: one fuzz op row: n_pages ranges past the 64-page zone so overflow
#: writes (illegal) mix with legal fills; host=False exercises the
#: dummy-write accounting
_FUZZ_ROW = st.tuples(
    st.sampled_from([E.OP_WRITE, E.OP_FINISH, E.OP_RESET]),
    st.integers(0, 3),
    st.integers(1, 70),
    st.booleans(),
)

#: one lane: (spec index, halve the effective capacity?, wear-aware?)
_LANE = st.tuples(st.integers(0, len(UNION_SPECS) - 1), st.booleans(),
                  st.booleans())


@settings(max_examples=8, deadline=None)
@given(st.lists(_FUZZ_ROW, min_size=1, max_size=24),
       st.lists(_LANE, min_size=3, max_size=5))
def test_mixed_spec_batch_bit_identical_to_per_spec_dispatches(
        rows, lanes):
    """A SUPERBLOCK+BLOCK+VCHUNK fleet in ONE ``run_programs``
    dispatch, each lane under its member's DynConfig bundle (optionally
    composed with a capacity shrink and a first-fit allocator), leaves
    every lane bit-identical to an independent dispatch on an engine
    built with that spec outright."""
    prog = pad_rows([(op, z, n, E.F_HOST if host else 0)
                     for op, z, n, host in rows])
    dyns, refs = [], []
    for spec_i, shrink, wear in lanes:
        spec = UNION_SPECS[spec_i]
        kw = dict(wear_aware=wear)
        if shrink:
            kw["zone_pages"] = HALF
        dyns.append(UNION.dyn(spec=spec, **kw))
        single = SINGLES[spec]
        refs.append(single.run(single.init_state(), prog,
                               single.dyn(**kw)))
    states, trace = UNION.run_batch(UNION.init_state(), np.stack(
        [prog] * len(lanes)), E.stack_dyn(dyns))
    for k, (spec_i, shrink, wear) in enumerate(lanes):
        spec = UNION_SPECS[spec_i]
        assert_lane_matches_single(
            states, trace, k, spec, *refs[k],
            ctx=f"lane {k} {spec.name} shrink={shrink} wear={wear}")


def test_union_primary_lane_equals_plain_engine_default_dyn():
    """A dyn-less run of a union engine defaults to the *primary*
    member's spec bundle (never a cross-member mix of maxima), so it
    must equal the plain primary-spec engine exactly -- with or
    without an explicit ``dyn(spec=...)``."""
    rows = [(E.OP_WRITE, z, 9 + z, E.F_HOST) for z in range(3)]
    rows += [(E.OP_FINISH, z, 0, 0) for z in range(3)]
    prog = pad_rows(rows)
    single = SINGLES[SUPERBLOCK]
    ref = single.run(single.init_state(), prog)
    states, trace = UNION.run_batch(
        UNION.init_state(), np.stack([prog]),
        E.stack_dyn([UNION.dyn(spec=SUPERBLOCK)]))
    assert_lane_matches_single(states, trace, 0, SUPERBLOCK, *ref,
                               ctx="primary lane")
    # spec-aware wear extraction matches the plain engine's
    assert np.array_equal(
        UNION.elem_wear(E.DeviceState(*[leaf[0] for leaf in states]),
                        SUPERBLOCK),
        single.elem_wear(ref[0]))
    # the dyn-less path (run / run_batch without a DynConfig) is the
    # primary member too, not the padded grid pretending to be a spec
    s_plain, _ = UNION.run(UNION.init_state(), prog)
    for name in ("host_pages", "dummy_pages", "block_erases",
                 "n_active"):
        assert int(getattr(s_plain, name)) == int(getattr(ref[0], name)), \
            name
    ids = UNION.member_element_ids(SUPERBLOCK)
    assert np.array_equal(np.asarray(s_plain.elem_wear)[ids],
                          np.asarray(ref[0].elem_wear)[: len(ids)])


# --------------------------------------------------------------------- #
# the fleet layer over the union config
# --------------------------------------------------------------------- #
def test_mixed_spec_fleet_rows_match_homogeneous_engines():
    """Evaluator rows of mixed-spec configs through the union engine
    equal the rows the same configs produce on engines built with each
    spec outright -- including the wear statistics, which must ignore
    the union grid's padding elements."""
    from repro.fleet import FleetConfig, evaluate_configs

    configs = [FleetConfig("dlwa_pair", 4, 8, True, True, BLOCK),
               FleetConfig("dlwa_write", 2, 16, False, True, SUPERBLOCK),
               FleetConfig("dlwa_pair", 2, 8, True, False, vchunk(2))]
    union_rows = evaluate_configs(UNION, configs, n_devices=3)
    for fc, mine in zip(configs, union_rows):
        ref = evaluate_configs(SINGLES[fc.spec], [fc], n_devices=3)[0]
        assert mine == ref, fc.describe()


def test_mixed_spec_fleet_matches_legacy_array_replay():
    """The per-op ``ZNSArray`` oracle of ``test_fleet.py``, on a
    mixed-spec batch: members are built with each config's actual
    element spec."""
    from repro.fleet import (FleetConfig, N_TENANTS, build_fleet_batch,
                             run_configs_legacy, run_fleet, runner)

    configs = [FleetConfig("dlwa_pair", 4, 8, True, True, BLOCK),
               FleetConfig("dlwa_write", 2, 16, False, True, SUPERBLOCK),
               FleetConfig("dlwa_pair", 2, 8, True, False, vchunk(2))]
    programs, dyn, merged = build_fleet_batch(UNION, configs, n_devices=3)
    res = run_fleet(UNION, programs, dyn=dyn, n_tenants=N_TENANTS)
    runner.assert_all_ok(res)
    legacy = run_configs_legacy(FLASH, SUPERBLOCK, configs, merged,
                                parallelism=4, n_devices=3, max_active=6)
    for k, (fc, rep) in enumerate(zip(configs, legacy)):
        lanes = np.arange(3 * k, 3 * (k + 1))
        mine = runner.config_report(res, UNION, lanes)
        assert mine["parity_pages"] == rep["parity_pages"], fc
        assert mine["dummy_pages"] == rep["dummy_pages"], fc
        assert mine["dlwa"] == pytest.approx(rep["dlwa"]), fc
        assert mine["block_erases"] == rep["total_block_erases"], fc
        assert mine["wear_cv"] == pytest.approx(rep["wear_cv"]), fc


def test_build_fleet_batch_rejects_non_member_spec():
    from repro.fleet import FleetConfig, build_fleet_batch

    fc = FleetConfig("dlwa_pair", 4, 8, False, True, hchunk(2))
    with pytest.raises(ValueError, match="not a member"):
        build_fleet_batch(UNION, [fc], n_devices=3)


# --------------------------------------------------------------------- #
# where a fleet batch's DynConfig lives: host leaves, one transfer
# --------------------------------------------------------------------- #
def _hetero_configs():
    """Mixed specs (incl. a per-member spec tuple), both policies, both
    allocators, and member counts 2 and 3, so the batch has pad lanes."""
    from repro.fleet import FleetConfig

    return [FleetConfig("dlwa_pair", 4, 8, True, True, BLOCK,
                        n_devices=3, alloc_policy="silent"),
            FleetConfig("dlwa_write", 2, 16, False, False, SUPERBLOCK,
                        n_devices=2),
            FleetConfig("dlwa_pair", 2, 8, False, True,
                        (vchunk(2), BLOCK), n_devices=3,
                        alloc_policy="silent"),
            FleetConfig("dlwa_write", 4, 8, True, False, vchunk(2),
                        n_devices=2)]


def test_build_fleet_batch_dyn_equals_device_scalar_stack():
    """The host-built stacked dyn equals, leaf by leaf in value, dtype
    and shape, the construction it replaces: every lane's fields as
    device scalars (``jnp.asarray``), then ``jnp.stack`` per field."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.fleet import build_fleet_batch

    configs = _hetero_configs()
    _, dyn, _ = build_fleet_batch(UNION, configs, n_devices=3)
    seg_pages = ZGEOM.parallelism * FLASH.pages_per_block
    lanes = []
    for fc in configs:
        mix = fc.specs_mix()
        for d in range(fc.n_devices):
            spec = mix[d % len(mix)]
            sv = UNION.cfg.member_values(spec)
            lanes.append(dict(
                zone_pages=seg_pages * fc.n_segments,
                max_active=UNION.cfg.max_active,
                n_zones=UNION.cfg.n_zones, wear_aware=fc.wear_aware,
                **dataclasses.asdict(sv),
                alloc_policy=(E.POLICY_SILENT
                              if fc.alloc_policy == "silent"
                              else E.POLICY_TRADITIONAL),
                wear_bound=E._BIG))
        lanes += [None] * (3 - fc.n_devices)    # pad lanes: defaults
    assert len(lanes) == 3 * len(configs) and None in lanes
    default = E.dyn_values(UNION.cfg)
    old = [E.DynConfig(**{
        f: jnp.asarray(v, bool if f == "wear_aware" else jnp.int32)
        for f, v in (lane or default).items()}) for lane in lanes]
    old = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *old)
    for f, new_leaf, old_leaf in zip(E.DynConfig._fields, dyn, old):
        assert isinstance(new_leaf, jax.Array), f
        assert new_leaf.dtype == old_leaf.dtype, f
        assert new_leaf.shape == old_leaf.shape == (len(lanes),), f
        assert np.array_equal(np.asarray(new_leaf),
                              np.asarray(old_leaf)), f


def test_make_dyn_host_leaves_and_stack_dyn_device_leaves():
    """``make_dyn`` leaves are 0-d numpy arrays of the field's dtype;
    ``stack_dyn`` returns ``jax.Array`` leaves and still takes
    ``_replace``d Python-int and ``jax.Array`` leaves in the field's
    dtype."""
    import jax
    import jax.numpy as jnp

    d = UNION.dyn(spec=BLOCK, alloc_policy="silent", wear_bound=3)
    for f, leaf in zip(E.DynConfig._fields, d):
        assert isinstance(leaf, np.ndarray) and leaf.shape == (), f
        assert leaf.dtype == (np.bool_ if f == "wear_aware"
                              else np.int32), f
    assert int(d.alloc_policy) == E.POLICY_SILENT
    assert int(d.wear_bound) == 3
    assert int(d.n_elements) == UNION.cfg.member_values(BLOCK).n_elements
    mixed = [d, d._replace(wear_bound=5, wear_aware=False),
             d._replace(zone_pages=jnp.asarray(HALF, jnp.int32))]
    st_ = E.stack_dyn(mixed)
    for f, leaf in zip(E.DynConfig._fields, st_):
        assert isinstance(leaf, jax.Array) and leaf.shape == (3,), f
        assert leaf.dtype == (jnp.bool_ if f == "wear_aware"
                              else jnp.int32), f
    assert np.asarray(st_.wear_bound).tolist() == [3, 5, 3]
    assert np.asarray(st_.wear_aware).tolist() == [True, False, True]
    assert int(st_.zone_pages[2]) == HALF
    assert E.dyn_values(UNION.cfg, st_, lane=1)["wear_bound"] == 5


def test_evaluator_repeats_do_not_grow_run_programs_cache():
    """Host-built dyns reach ``run_programs`` with one abstract
    signature: repeated same-shape dispatches of a batch with pad lanes
    compile once."""
    from repro.fleet import Evaluator

    ev = Evaluator(UNION, n_devices=3, pad_quantum=64)
    configs = _hetero_configs()
    first = ev.evaluate(configs)
    cache = ev.jit_cache()
    for _ in range(2):
        assert ev.evaluate(configs) == first
        assert ev.jit_cache() == cache


def test_search_space_spec_axis_codec():
    from repro.fleet import SearchSpace

    space = SearchSpace(segments=(4, 2), chunks=(8, 16),
                        specs=UNION_SPECS)
    assert len(space) == 2 * 2 * 2 * 2 * 2 * 3
    for fc in space.grid():
        assert space.decode(space.encode(fc)) == fc
    names = {fc.describe() for fc in space.grid()}
    assert len(names) == len(space)  # spec axis keeps names unique
