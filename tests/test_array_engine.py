"""Engine-native ZNS-RAID vs the object ``ZNSArray`` oracle.

Every test drives both surfaces through one logical command list
(:func:`repro.array.apply_commands`) and demands *bit-exact* equality
of ``report()`` / ``device_reports()`` -- the same oracle relationship
``LegacyZNSDevice`` has to ``ZoneEngine``, one layer up.  Covers the
chunk x parity x member-count x spec-mix grid (fuzzed), degraded reads
past a failed member, rebuild round-trips, the batched rebuild storm,
and the ``devices`` search axis.
"""

import random

import numpy as np
import pytest

from repro.array import (ArrayEngine, ArrayGeometry, StormScenario,
                         ZNSArray, apply_commands, fill_commands,
                         rebuild_storm, run_array_batch)
from repro.array.engine import _legacy_array
from repro.core import engine as E
from repro.core import timing
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.geometry import FlashGeometry, ZoneGeometry


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1,
                         blocks_per_lun=16, pages_per_block=4,
                         page_bytes=4096)


def tiny_geoms():
    return tiny_flash(), ZoneGeometry(4, n_segments=4)


def build_pair(n_devices, *, chunk_pages=None, parity=False,
               specs=SUPERBLOCK, max_active=6, wear_aware=None):
    """(ArrayEngine, oracle ZNSArray) over the same tiny geometry."""
    flash, zone = tiny_geoms()
    eng_arr = ArrayEngine.build(flash, zone, specs, n_devices=n_devices,
                                chunk_pages=chunk_pages, parity=parity,
                                max_active=max_active,
                                wear_aware=wear_aware)
    legacy = _legacy_array(flash, zone, eng_arr.geom,
                           eng_arr.member_specs, max_active=max_active)
    if wear_aware is not None:
        for d in legacy.devices:
            d.wear_aware = wear_aware
    return eng_arr, legacy


def assert_bit_identical(eng_arr: ArrayEngine, legacy: ZNSArray):
    er, lr = eng_arr.report(), legacy.report()
    assert er.keys() == lr.keys()
    for k in er:
        assert er[k] == lr[k], k
    for ed, ld in zip(eng_arr.device_reports(), legacy.device_reports()):
        assert ed.keys() == ld.keys()
        for k in ed:
            assert ed[k] == ld[k], k


# --------------------------------------------------------------------- #
# fuzzed differential: chunk x parity x members x spec mix
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n_devices,chunk,parity", [
    (2, None, False), (2, 8, False),
    (3, None, True), (3, 4, True),
    (4, 16, True), (4, 8, False),
])
def test_fuzzed_differential(n_devices, chunk, parity):
    eng_arr, legacy = build_pair(n_devices, chunk_pages=chunk,
                                 parity=parity)
    rng = random.Random(1000 * n_devices + (chunk or 0) + int(parity))
    zp = eng_arr.zone_pages
    wp = {z: 0 for z in range(3)}
    cmds = []
    for _ in range(60):
        z = rng.randrange(3)
        verb = rng.choice(["write", "write", "write", "finish",
                           "reset", "read"])
        if verb == "write" and wp[z] is not None:
            n = rng.randrange(1, max(2, zp - wp[z] + 1))
            n = min(n, zp - wp[z])
            if n <= 0:
                continue
            cmds.append(("write", z, n, rng.random() < 0.9))
            wp[z] += n
            if wp[z] == zp:
                wp[z] = None        # FULL
        elif verb == "finish":
            cmds.append(("finish", z))
            wp[z] = None
        elif verb == "reset":
            cmds.append(("reset", z))
            wp[z] = 0
        elif verb == "read" and wp[z] and wp[z] > 0:
            offs = sorted(rng.sample(range(wp[z]),
                                     min(4, wp[z])))
            cmds.append(("read", z, offs))
    apply_commands(eng_arr, cmds)
    apply_commands(legacy, cmds)
    assert_bit_identical(eng_arr, legacy)


@pytest.mark.parametrize("specs", [
    (SUPERBLOCK, BLOCK, SUPERBLOCK),
    (BLOCK, vchunk(2), SUPERBLOCK),
])
def test_mixed_member_specs_differential(specs):
    """Heterogeneous member specs run per-lane through one union
    engine and still match the oracle exactly."""
    eng_arr, legacy = build_pair(3, parity=True, specs=specs)
    assert eng_arr.member_specs == tuple(specs)
    cmds = fill_commands(eng_arr.zone_pages, n_zones=2, occupancy=0.7,
                         churn=2)
    apply_commands(eng_arr, cmds)
    apply_commands(legacy, cmds)
    assert_bit_identical(eng_arr, legacy)


def test_error_message_equality():
    """The engine front-end raises the oracle's exact strings."""
    cases = [
        [("write", 0, 10_000, True)],                      # overflow
        [("finish", 0), ("write", 0, 1, True)],            # FULL write
        [("read", 1, [0])],                                # unmapped
    ]
    for cmds in cases:
        eng_arr, legacy = build_pair(2, parity=False)
        with pytest.raises(RuntimeError) as ee:
            apply_commands(eng_arr, cmds)
        with pytest.raises(RuntimeError) as le:
            apply_commands(legacy, cmds)
        assert str(ee.value) == str(le.value)

    # parity-off data loss on a failed member (40 pages span every
    # member at the default one-segment chunk, so the read must cross
    # the failed one)
    eng_arr, legacy = build_pair(3, parity=False)
    prefix = [("write", 0, 40, True), ("fail", 1)]
    apply_commands(eng_arr, prefix)
    apply_commands(legacy, prefix)
    with pytest.raises(RuntimeError) as ee:
        eng_arr.zone_read(0, np.arange(40))
    with pytest.raises(RuntimeError) as le:
        legacy.zone_read(0, np.arange(40))
    assert str(ee.value) == str(le.value)
    assert "parity is off" in str(ee.value)


# --------------------------------------------------------------------- #
# degraded reads + rebuild round-trips
# --------------------------------------------------------------------- #
def test_degraded_read_routes_around_failed_member():
    eng_arr, legacy = build_pair(3, parity=True)
    cmds = [("write", 0, 40, True), ("fail", 2),
            ("read", 0, list(range(40)))]
    apply_commands(eng_arr, cmds)
    apply_commands(legacy, cmds)
    # the engine plan never touches the failed member, and every
    # surviving offset lands inside that member's written extent
    plan = eng_arr.zone_read(0, np.arange(40))
    assert 2 not in plan
    for member, offs in plan.items():
        assert max(offs) < eng_arr.member_wp(0, member)
    assert_bit_identical(eng_arr, legacy)


@pytest.mark.parametrize("n_devices,chunk", [(3, None), (4, 8)])
def test_rebuild_round_trip(n_devices, chunk):
    eng_arr, legacy = build_pair(n_devices, chunk_pages=chunk,
                                 parity=True)
    zp = eng_arr.zone_pages
    written = max(1, int(zp * 0.8))   # reads stay in the host extent
    cmds = (fill_commands(zp, n_zones=2, occupancy=0.8)
            + [("write", 2, zp // 3, True),       # partial zone too
               ("fail", 0),
               ("read", 0, list(range(0, written, 7))),
               ("rebuild", 0),
               ("write", 2, zp // 4, True),       # post-rebuild traffic
               ("read", 2, list(range(zp // 4)))])
    apply_commands(eng_arr, cmds)
    apply_commands(legacy, cmds)
    assert not eng_arr.failed and not legacy.failed
    assert_bit_identical(eng_arr, legacy)


def test_rebuild_requires_parity_and_single_failure():
    eng_arr, _ = build_pair(3, parity=False)
    eng_arr.fail_device(0)
    with pytest.raises(RuntimeError, match="requires parity"):
        eng_arr.rebuild_device(0)
    eng_arr, _ = build_pair(3, parity=True)
    eng_arr.fail_device(0)
    with pytest.raises(RuntimeError, match="second device failure"):
        eng_arr.fail_device(1)


# --------------------------------------------------------------------- #
# batched dispatch + timing
# --------------------------------------------------------------------- #
def test_batched_arrays_match_sequential_runs():
    """K arrays in ONE dispatch report exactly what each reports when
    run alone."""
    flash, zone = tiny_geoms()
    shared = E.ZoneEngine(flash, zone, SUPERBLOCK, max_active=6)

    def make(i):
        a = ArrayEngine(shared, ArrayGeometry(2 + i % 2, 8, bool(i % 2)))
        apply_commands(a, fill_commands(
            a.zone_pages, n_zones=2, occupancy=0.4 + 0.1 * i))
        return a

    batch = [make(i) for i in range(4)]
    solo = [make(i) for i in range(4)]
    run_array_batch(batch, pad_quantum=16)
    for b, s in zip(batch, solo):
        assert b.report() == s.report()
        assert b.device_reports() == s.device_reports()


def test_fleet_timing_per_op_read_write_rates():
    """Array timing books reads at the read+xfer rate and writes at the
    program+xfer rate -- the per-op t_page path through
    simulate_fleet_ops."""
    eng_arr, _ = build_pair(2, parity=False)
    apply_commands(eng_arr, [("write", 0, 16, True),
                             ("read", 0, list(range(16)))])
    t = eng_arr.fleet_timing()
    assert t["fleet_pages"] > 0
    assert t["fleet_makespan_s"] > 0
    flash = tiny_flash()
    # scalar t_page still broadcasts (bit-compat with pre-array callers)
    cols = np.zeros((1, 2), np.int32)
    pages = np.array([[4, 4]], np.int32)
    ten = np.zeros((1, 2), np.int32)
    ops = np.zeros((1, 2), np.int32)
    _, _, scalar = timing.simulate_fleet_ops(
        cols, pages, ten, np.float32(1e-3), flash.n_luns, 1)
    _, _, perop = timing.simulate_fleet_ops(
        cols, pages, ten, np.full((1, 2), 1e-3, np.float32),
        flash.n_luns, 1)
    assert np.array_equal(np.asarray(scalar), np.asarray(perop))
    del ops


# --------------------------------------------------------------------- #
# rebuild storm
# --------------------------------------------------------------------- #
def test_rebuild_storm_batched_and_recompile_stable():
    from repro.obs import ObsConfig
    from repro.obs.profile import RecompileCounter

    flash, zone = tiny_geoms()
    eng = E.ZoneEngine(flash, zone, SUPERBLOCK, max_active=6)
    scenarios = [StormScenario(n_devices=3, n_zones_filled=1,
                               occupancy=0.5),
                 StormScenario(n_devices=4, n_zones_filled=1,
                               occupancy=0.6, chunk_pages=8)]
    obs = ObsConfig(n_buckets=8, n_tenants=3)
    counter = RecompileCounter(run_programs=E.run_programs,
                               simulate_fleet_ops=timing.simulate_fleet_ops)
    out = rebuild_storm(eng, scenarios, obs=obs, pad_quantum=16)
    assert len(out["scenarios"]) == 2
    assert len(out["telemetry"]) == 2
    for rep in out["scenarios"]:
        assert rep["rebuild_pages"] > 0
        assert rep["rebuild_read_pages"] > 0
        assert rep["rebuild_traffic_pages"] >= rep["rebuild_pages"]
        assert rep["host_makespan_s"] > 0
        # contention can only slow the host stream down
        assert rep["rebuild_interference"] >= 1.0
    before = counter.counts()
    again = rebuild_storm(eng, scenarios, obs=obs, pad_quantum=16)
    assert sum(counter.delta(before).values()) == 0
    assert again["scenarios"] == out["scenarios"]


def test_rebuild_storm_empty():
    flash, zone = tiny_geoms()
    eng = E.ZoneEngine(flash, zone, SUPERBLOCK, max_active=6)
    assert rebuild_storm(eng, []) == {"scenarios": [],
                                      "telemetry": None}


# --------------------------------------------------------------------- #
# the devices search axis
# --------------------------------------------------------------------- #
def test_search_space_devices_axis_codec():
    from repro.fleet import FleetConfig, SearchSpace, grid_space

    space = SearchSpace(mixes=("dlwa_pair",), segments=(4,), chunks=(8,),
                        specs=(SUPERBLOCK,), devices=(3, 4))
    assert len(space.axes) == 7
    for fc in space.grid():
        assert space.decode(space.encode(fc)) == fc
        assert fc.describe().endswith(f"_d{fc.n_devices}")
    # a default space keeps 6-gene vectors (seeded trajectories intact)
    assert len(SearchSpace().axes) == 6
    with pytest.raises(ValueError, match="no devices axis"):
        SearchSpace().encode(FleetConfig("dlwa_pair", 4, 8, True, True,
                                         n_devices=3))
    assert len(grid_space(mixes=("dlwa_pair",), segments=(4,),
                          chunks=(8,), parities=(False,), wear=(True,),
                          devices=(2, 3))) == 2


def test_evaluator_mixed_member_counts_match_legacy():
    """Configs with different n_devices in ONE padded dispatch score
    exactly like the per-config legacy array replay."""
    from repro.fleet import (FleetConfig, N_TENANTS, build_fleet_batch,
                             run_configs_legacy, run_fleet)
    from repro.fleet import runner

    flash, zone = tiny_geoms()
    eng = E.ZoneEngine(flash, zone, (SUPERBLOCK, BLOCK), max_active=6)
    configs = [FleetConfig("dlwa_pair", 4, 8, True, True, n_devices=3),
               FleetConfig("dlwa_write", 2, 16, False, True,
                           n_devices=4),
               FleetConfig("dlwa_pair", 2, 8, True, False,
                           spec=(SUPERBLOCK, BLOCK), n_devices=3)]
    programs, dyn, merged = build_fleet_batch(eng, configs, n_devices=4)
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS)
    runner.assert_all_ok(res)
    legacy = run_configs_legacy(flash, SUPERBLOCK, configs, merged,
                                parallelism=4, n_devices=4,
                                max_active=6)
    nd_max = 4
    for k, (fc, rep) in enumerate(zip(configs, legacy)):
        lanes = np.arange(k * nd_max, k * nd_max + fc.n_devices)
        mine = runner.config_report(res, eng, lanes)
        assert mine["parity_pages"] == rep["parity_pages"], fc
        assert mine["dummy_pages"] == rep["dummy_pages"], fc
        assert mine["dlwa"] == pytest.approx(rep["dlwa"], abs=1e-9), fc
        assert mine["block_erases"] == rep["total_block_erases"], fc
