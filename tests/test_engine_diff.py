"""Differential tests: pytree engine vs the legacy stateful device.

Three layers of equivalence, all required to be *exact*:

1. random op sequences (hypothesis-generated) replayed
   through the legacy ``LegacyZNSDevice``, the engine-backed ``ZNSDevice``
   shim, and the raw ``run_program`` scan must leave identical
   wear/avail/pages/zone-map state, counters, and zone tables -- illegal
   ops included (legacy ``RuntimeError`` <-> engine ``ok=0`` with the same
   partial effects);
2. the paper's dlwa / interference / write benchmarks driven as op
   programs must reproduce the legacy per-op metrics exactly (DLWA, dummy
   pages, wear histogram, and even the timing-model outputs, since the
   reconstructed IO streams are bit-identical);
3. the vmapped sweep executor must equal per-program scans.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core import workloads
from repro.core.device import ZNSDevice
from repro.core.device_legacy import LegacyZNSDevice
from repro.core.elements import (BLOCK, FIXED, SUPERBLOCK, hchunk, vchunk)
from repro.core.geometry import FlashGeometry, ZoneGeometry, zn540

SPECS = [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK, FIXED]


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=8,
                         pages_per_block=4, page_bytes=4096)


def assert_same_device_state(dev, leg, ctx=""):
    assert np.array_equal(dev.elem_wear, leg.elem_wear), f"wear {ctx}"
    assert np.array_equal(dev.elem_avail, leg.elem_avail), f"avail {ctx}"
    assert np.array_equal(dev.elem_pages, leg.elem_pages), f"pages {ctx}"
    assert np.array_equal(dev.elem_zone, leg.elem_zone), f"zone {ctx}"
    assert dev.host_pages == leg.host_pages, ctx
    assert dev.dummy_pages == leg.dummy_pages, ctx
    assert dev.block_erases == leg.block_erases, ctx
    assert dev.dlwa == leg.dlwa, ctx
    assert dev.n_active == leg.n_active, ctx
    for z in range(dev.n_zones):
        a, b = dev.zones[z], leg.zones[z]
        assert (a.state.name, a.wp, a.host_wp) == \
            (b.state.name, b.wp, b.host_wp), f"zone {z} {ctx}"
        if a.elements is not None and b.elements is not None:
            assert np.array_equal(a.elements, b.elements), f"map {z} {ctx}"


def assert_scan_matches_legacy(eng, state, leg, ctx=""):
    n = eng.cfg.n_elements
    assert np.array_equal(np.asarray(state.elem_wear[:n]),
                          leg.elem_wear), f"wear {ctx}"
    assert np.array_equal(np.asarray(state.elem_avail[:n]),
                          leg.elem_avail), f"avail {ctx}"
    assert np.array_equal(np.asarray(state.elem_pages[:n]),
                          leg.elem_pages), f"pages {ctx}"
    assert np.array_equal(np.asarray(state.elem_zone[:n]),
                          leg.elem_zone), f"map {ctx}"
    assert int(state.host_pages) == leg.host_pages, ctx
    assert int(state.dummy_pages) == leg.dummy_pages, ctx
    assert int(state.block_erases) == leg.block_erases, ctx
    assert int(state.n_active) == leg.n_active, ctx
    zs = np.asarray(state.zone_state)
    wp = np.asarray(state.zone_wp)
    hwp = np.asarray(state.zone_host_wp)
    for z in range(eng.cfg.n_zones):
        info = leg.zones[z]
        assert zs[z] == info.state.value, f"zone {z} state {ctx}"
        assert wp[z] == info.wp and hwp[z] == info.host_wp, f"zone {z} {ctx}"
    assert np.array_equal(eng.block_wear(state), leg.block_wear()), ctx


# --------------------------------------------------------------------- #
# 1. random op sequences, illegal ops included
# --------------------------------------------------------------------- #
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, len(SPECS) - 1))
def test_differential_random_op_sequences(seed, spec_i):
    spec = SPECS[spec_i]
    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    rng = np.random.default_rng(seed)
    dev = ZNSDevice(flash, zone, spec, max_active=3)
    leg = LegacyZNSDevice(flash, zone, spec, max_active=3)
    eng = dev.engine
    rows = []
    for i in range(30):
        op = int(rng.integers(0, 3))
        z = int(rng.integers(0, 4))
        n = int(rng.integers(1, leg.zone_pages + 2))  # may overflow the zone
        if op == 0:
            rows.append((E.OP_WRITE, z, n, E.F_HOST))
        elif op == 1:
            rows.append((E.OP_FINISH, z, 0, 0))
        else:
            rows.append((E.OP_RESET, z, 0, 0))
        outcomes = []
        for d in (dev, leg):
            try:
                if op == 0:
                    d.zone_write(z, n)
                elif op == 1:
                    d.zone_finish(z)
                else:
                    d.zone_reset(z)
                outcomes.append("ok")
            except RuntimeError:
                outcomes.append("err")
        ctx = f"seed={seed} spec={spec.name} i={i} op={op} z={z} n={n}"
        assert outcomes[0] == outcomes[1], ctx
        assert_same_device_state(dev, leg, ctx)
    # the same sequence as ONE compiled scan
    state, trace = eng.run(eng.init_state(), E.encode_program(rows))
    assert_scan_matches_legacy(eng, state, leg,
                               f"seed={seed} spec={spec.name}")
    # shim and scan agree op-by-op on the pytree too
    assert np.array_equal(np.asarray(state.elem_wear),
                          np.asarray(dev.state.elem_wear))


#: one fuzz op row: (opcode, zone, n_pages, host).  n_pages ranges past
#: the tiny geometry's 32-page zone so overflow writes (illegal) mix
#: with legal fills; dummy (host=False) writes exercise the
#: dummy-page accounting paths.
_FUZZ_ROW = st.tuples(
    st.sampled_from([E.OP_WRITE, E.OP_FINISH, E.OP_RESET]),
    st.integers(0, 3),
    st.integers(1, 34),
    st.booleans(),
)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, len(SPECS) - 1), st.integers(1, 4),
       st.lists(_FUZZ_ROW, min_size=1, max_size=40))
def test_differential_fuzz_programs(spec_i, max_active, rows):
    """Strategy-generated mixed valid/illegal programs: the legacy
    device, the engine-backed shim, and ONE ``run_program`` scan must
    leave exactly the same device state, and the scan's per-op ``ok``
    flags must line up with where the legacy device raised."""
    spec = SPECS[spec_i]
    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    dev = ZNSDevice(flash, zone, spec, max_active=max_active)
    leg = LegacyZNSDevice(flash, zone, spec, max_active=max_active)
    legal = []
    for i, (op, z, n, host) in enumerate(rows):
        outcomes = []
        for d in (dev, leg):
            try:
                if op == E.OP_WRITE:
                    d.zone_write(z, n, host=host)
                elif op == E.OP_FINISH:
                    d.zone_finish(z)
                else:
                    d.zone_reset(z)
                outcomes.append(True)
            except RuntimeError:
                outcomes.append(False)
        ctx = f"spec={spec.name} ma={max_active} i={i} row={rows[i]}"
        assert outcomes[0] == outcomes[1], ctx
        legal.append(outcomes[1])
        assert_same_device_state(dev, leg, ctx)
    prog = E.encode_program(
        [(op, z, n, E.F_HOST if host else 0)
         for op, z, n, host in rows])
    eng = dev.engine
    state, trace = eng.run(eng.init_state(), prog)
    ctx = f"spec={spec.name} ma={max_active}"
    assert_scan_matches_legacy(eng, state, leg, ctx)
    # ok=0 exactly where the legacy device raised (WRITE-only; FINISH /
    # RESET never raise and always report ok)
    assert np.asarray(trace.ok).tolist() == legal, ctx
    # the scan's final pytree equals the shim's, leaf for leaf
    for mine, shim in zip(state, dev.state):
        assert np.array_equal(np.asarray(mine), np.asarray(shim)), ctx


@pytest.mark.parametrize("spec", [BLOCK, vchunk(2), SUPERBLOCK, FIXED],
                         ids=lambda s: s.name)
def test_differential_wear_oblivious_allocation(spec):
    """wear_aware=False (the ConfZNS++-style first-fit policy): selection
    is by column, but slot arrangement still ranks by wear -- must stay
    bit-identical to legacy under wear-divergent churn."""
    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    dev = ZNSDevice(flash, zone, spec, wear_aware=False)
    leg = LegacyZNSDevice(flash, zone, spec, wear_aware=False)
    for i in range(12):
        z = i % 3
        for d in (dev, leg):
            d.zone_write(z, 3 + i)        # partial fill: uneven wear
            d.zone_finish(z)
            d.zone_reset(z)
        assert_same_device_state(dev, leg, f"{spec.name} i={i}")


# --------------------------------------------------------------------- #
# 2. paper benchmark programs: exact metric parity
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("spec", [SUPERBLOCK, FIXED], ids=lambda s: s.name)
def test_dlwa_program_matches_legacy(spec):
    flash, zone = zn540()
    eng = workloads.make_engine(flash, zone, spec, max_active=28)
    for occ in (0.1, 0.4, 0.9):
        leg = LegacyZNSDevice(flash, zone, spec, max_active=28)
        a = workloads.dlwa_benchmark(leg, occupancy=occ, n_zones=4)
        b = workloads.dlwa_benchmark_engine(eng, occupancy=occ, n_zones=4)
        assert a == b, (spec.name, occ)
        # wear histogram parity for the final state of the program
        prog = workloads.dlwa_program(eng, occupancy=occ, n_zones=4)
        state, _ = eng.run(eng.init_state(), prog)
        assert np.array_equal(eng.block_wear(state), leg.block_wear())


@pytest.mark.parametrize("spec", [SUPERBLOCK, FIXED], ids=lambda s: s.name)
def test_interference_program_matches_legacy(spec):
    """Fused finish+host-write program: identical metrics AND identical
    timing-model outputs (the rebuilt IO streams are bit-equal)."""
    flash, zone = zn540()
    eng = workloads.make_engine(flash, zone, spec, max_active=28)
    for conc in (1, 3):
        leg = LegacyZNSDevice(flash, zone, spec, max_active=28)
        a = workloads.interference_benchmark(leg, concurrency=conc)
        b = workloads.interference_benchmark_engine(eng, concurrency=conc)
        assert a == b, (spec.name, conc)


def test_write_program_matches_legacy():
    flash, zone = zn540()
    eng = workloads.make_engine(flash, zone, SUPERBLOCK, max_active=28)
    leg = LegacyZNSDevice(flash, zone, SUPERBLOCK, max_active=28)
    a = workloads.write_benchmark(leg, request_kib=16, n_jobs=4,
                                  mib_per_job=4)
    b = workloads.write_benchmark_engine(eng, request_kib=16, n_jobs=4,
                                         mib_per_job=4)
    assert a == b


def test_shim_trace_streams_match_legacy():
    """trace=True IO streams (write + FINISH padding) are bit-identical."""
    flash, zone = zn540()
    dev = ZNSDevice(flash, zone, SUPERBLOCK, max_active=28)
    leg = LegacyZNSDevice(flash, zone, SUPERBLOCK, max_active=28)
    for z in range(4):
        fill = max(1, int(dev.zone_pages * (0.2 + 0.2 * z)))
        t1 = dev.zone_write(z, fill, trace=True)
        t2 = leg.zone_write(z, fill, trace=True)
        assert np.array_equal(t1.luns, t2.luns)
        assert np.array_equal(t1.channels, t2.channels)
        f1 = dev.zone_finish(z, trace=True)
        f2 = leg.zone_finish(z, trace=True)
        assert (f1 is None) == (f2 is None)
        if f1 is not None:
            assert np.array_equal(f1.luns, f2.luns)
            assert np.array_equal(f1.channels, f2.channels)


def test_headline_dlwa_matches_legacy_oracle():
    """The paper-headline DLWA figure (paired traditional/silent lanes
    over one union engine, ONE batched dispatch) must agree per
    occupancy point with per-op ``LegacyZNSDevice`` oracles: the
    traditional lane with a legacy device built on the whole-zone
    hchunk spec, the silent lane with a legacy BLOCK device (page
    accounting is policy-independent; see
    ``tests/test_silentzns_property.py``)."""
    from repro.core import headline

    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    eng = headline.build_headline_engine(flash, zone, max_active=3)
    occs = (0.1, 0.5, 0.9)
    fig = headline.dlwa_figure(eng, occs, n_zones=2)
    oracle_specs = {"traditional_dlwa": headline.traditional_spec(zone),
                    "silent_dlwa": BLOCK}
    for key, spec in oracle_specs.items():
        for i, occ in enumerate(occs):
            leg = LegacyZNSDevice(flash, zone, spec, max_active=3)
            ref = workloads.dlwa_benchmark(leg, occupancy=occ, n_zones=2)
            assert fig[key][i] == ref["dlwa"], (key, occ)
    # the gated reduction is exactly the 10%-point pairing of the two
    r = headline.dlwa_reduction_at(fig, 0.1)
    assert r == 1.0 - fig["silent_dlwa"][0] / fig["traditional_dlwa"][0]


# --------------------------------------------------------------------- #
# 3. vmapped sweep == per-program scans
# --------------------------------------------------------------------- #
def test_vmapped_sweep_equals_single_scans():
    flash, zone = zn540()
    eng = workloads.make_engine(flash, zone, SUPERBLOCK, max_active=28)
    occs = [0.1, 0.3, 0.5, 0.7, 0.9]
    sweep = workloads.dlwa_sweep_engine(eng, occs, n_zones=4)
    for row, occ in zip(sweep, occs):
        single = workloads.dlwa_benchmark_engine(eng, occupancy=occ,
                                                 n_zones=4)
        assert row == single, occ


# --------------------------------------------------------------------- #
# DynConfig override validation (regressions: silent out-of-range
# overrides indexed past the padded static tables, silent FIXED shrink
# corrupted metrics)
# --------------------------------------------------------------------- #
def test_make_dyn_rejects_out_of_range_overrides():
    """zone_pages / n_zones / max_active beyond the padded static
    EngineConfig used to be accepted silently and index past the padded
    tables (wrong metrics, no error); they must raise eagerly, naming
    the offending field."""
    flash = tiny_flash()
    eng = E.ZoneEngine(flash, ZoneGeometry(4, 2), SUPERBLOCK,
                       max_active=3)
    cfg = eng.cfg
    for field, bad in [("zone_pages", cfg.zone_pages + 1),
                       ("zone_pages", 0),
                       ("n_zones", cfg.n_zones + 1),
                       ("n_zones", 0),
                       ("max_active", cfg.max_active + 1),
                       ("max_active", 0)]:
        with pytest.raises(ValueError, match=field):
            E.make_dyn(cfg, **{field: bad})
        with pytest.raises(ValueError, match=field):
            eng.dyn(**{field: bad})
    # in-range values (the documented override surface) still pass
    d = eng.dyn(zone_pages=cfg.zone_pages // 2, n_zones=1, max_active=1)
    assert int(d.zone_pages) == cfg.zone_pages // 2


def test_make_dyn_rejects_fixed_capacity_shrink():
    """Shrinking zone_pages on a FIXED-kind lane is documented illegal
    (the element *is* the whole static zone) and was guarded only in
    ``build_fleet_batch``; direct ``make_dyn`` / ``run_batch`` callers
    silently corrupted metrics.  Both construction paths must raise."""
    flash = tiny_flash()
    eng = E.ZoneEngine(flash, ZoneGeometry(4, 2), FIXED, max_active=3)
    half = eng.cfg.zone_pages // 2
    with pytest.raises(ValueError, match="FIXED"):
        E.make_dyn(eng.cfg, zone_pages=half)
    with pytest.raises(ValueError, match="FIXED"):
        eng.dyn(zone_pages=half)   # the run/run_batch dyn entry point
    # full capacity stays legal on FIXED lanes
    assert int(eng.dyn(zone_pages=eng.cfg.zone_pages).zone_pages) \
        == eng.cfg.zone_pages
    # non-FIXED kinds keep the established shrink semantics
    blk = E.ZoneEngine(flash, ZoneGeometry(4, 2), BLOCK, max_active=3)
    assert int(blk.dyn(zone_pages=blk.cfg.zone_pages // 2).zone_pages) \
        == blk.cfg.zone_pages // 2


def test_make_dyn_rejects_bad_alloc_policy():
    """The alloc_policy axis must validate eagerly, naming the field:
    an unknown policy string/int used to be conceivable as a silently
    traced garbage branch selector; and FIXED lanes have no block
    collection to vary, so 'silent' on FIXED is a construction-time
    error, not a runtime misallocation."""
    flash = tiny_flash()
    eng = E.ZoneEngine(flash, ZoneGeometry(4, 2), BLOCK, max_active=3)
    for bad in ("silentzns", "SILENT", ""):
        with pytest.raises(ValueError, match="alloc_policy"):
            E.make_dyn(eng.cfg, alloc_policy=bad)
        with pytest.raises(ValueError, match="alloc_policy"):
            eng.dyn(alloc_policy=bad)
    with pytest.raises(ValueError, match="alloc_policy"):
        eng.dyn(alloc_policy=7)
    fixed = E.ZoneEngine(flash, ZoneGeometry(4, 2), FIXED, max_active=3)
    with pytest.raises(ValueError, match="alloc_policy"):
        fixed.dyn(alloc_policy="silent")
    with pytest.raises(ValueError, match="wear_bound"):
        eng.dyn(wear_bound=-1)
    # the documented surface still passes: names, ints, and the default
    assert int(eng.dyn(alloc_policy="silent").alloc_policy) \
        == E.POLICY_SILENT
    assert int(eng.dyn(alloc_policy=E.POLICY_SILENT).alloc_policy) \
        == E.POLICY_SILENT
    assert int(eng.dyn().alloc_policy) == E.POLICY_TRADITIONAL
    assert int(fixed.dyn(alloc_policy="traditional").alloc_policy) \
        == E.POLICY_TRADITIONAL
    assert int(eng.dyn(wear_bound=2).wear_bound) == 2


# --------------------------------------------------------------------- #
# shim-specific invariants
# --------------------------------------------------------------------- #
def test_warmup_alloc_does_not_mutate_state():
    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    for dev in (ZNSDevice(flash, zone, BLOCK),
                LegacyZNSDevice(flash, zone, BLOCK)):
        before = dev.elem_wear.copy(), dev.elem_avail.copy()
        dev.warmup_alloc()
        assert np.array_equal(dev.elem_wear, before[0])
        assert np.array_equal(dev.elem_avail, before[1])
        assert dev.host_pages == 0 and dev.alloc_calls == 0


def test_alloc_latency_benchmark_excludes_compile():
    """After the warmup fix, no timed sample should be compile-sized
    (>100x the median) on a freshly constructed device."""
    flash = tiny_flash()
    zone = ZoneGeometry(parallelism=4, n_segments=2)
    dev = ZNSDevice(flash, zone, BLOCK)
    r = workloads.alloc_latency_benchmark(dev, n_allocs=8)
    lat = np.asarray(dev.alloc_latencies_us)
    assert r["n_allocs"] == len(lat)
    assert lat.max() < max(100.0 * r["median_us"], 5e4)
