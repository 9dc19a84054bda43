"""The benchmark's copies of the references agree with the program's
own on a small case: the device model with the program's verifier and
engine, the legacy device with the program's legacy device, the clock
with the program's timing scan, and the derived configuration with the
engine's."""

import numpy as np
import pytest

import repro.storage as storage
from repro.check import verify_programs
from repro.core.device_legacy import LegacyZNSDevice
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.engine import ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry

from reference import clock, legacy
from reference import elements as rel
from reference import geometry as rgeo
from reference.check import run_reference
from reference.static import lane_values, union_static

import tiny

SPECS = (SUPERBLOCK, BLOCK, vchunk(2))
RSPECS = (rel.SUPERBLOCK, rel.BLOCK, rel.vchunk(2))
POLICIES = ("traditional", "silent")


@pytest.fixture(scope="module")
def replay():
    flash = FlashGeometry(**tiny.FLASH)
    eng = ZoneEngine(flash, ZoneGeometry(**tiny.ZONE), SPECS, max_active=14)
    lanes = [(s, p) for p in POLICIES for s in range(3)]
    dyns = [eng.dyn(spec=SPECS[s], alloc_policy=p) for s, p in lanes]
    recs = storage.workload_programs(eng, "lsm", n_lanes=2)
    res = storage.replay_recorders(eng, [recs[k % 2] for k in range(6)],
                                   dyns=dyns, n_tenants=3)
    rflash = rgeo.FlashGeometry(**tiny.FLASH)
    static = union_static(rflash, rgeo.ZoneGeometry(**tiny.ZONE), RSPECS, 14)
    values = [lane_values(static, RSPECS[s], alloc_policy=p)
              for s, p in lanes]
    ref = run_reference(static, rflash, np.asarray(res.programs), values,
                        res.parity_tenant)
    return eng, res, lanes, static, values, ref


def test_static_values_match_the_engine(replay):
    eng, _, _, static, _, _ = replay
    for f in ("n_elements", "n_groups", "per_group", "take", "zone_groups",
              "n_slots", "zone_pages", "n_zones"):
        assert getattr(static, f) == getattr(eng.cfg, f), f
    for spec, v in eng.cfg.members:
        assert static.members[spec.name] == vars(v)


def test_model_matches_verifier_and_engine(replay):
    eng, res, _, _, _, ref = replay
    want = np.stack([r.ok for r in verify_programs(res.cfg, res.programs,
                                                   res.dyn)])
    assert np.array_equal(ref["ok"], want)
    for k in ("ok", "host_delta", "dummy_delta", "erase_delta"):
        assert np.array_equal(ref[k], np.asarray(getattr(res, k))), k
    wear = np.asarray(res.states.elem_wear)[:, :eng.cfg.n_elements]
    assert np.array_equal(np.stack([s["elem_wear"] for s in ref["states"]]),
                          wear)


def test_clock_matches_the_timing_scan(replay):
    _, res, _, _, _, ref = replay
    for k in ("completions", "latencies", "makespans"):
        assert np.array_equal(
            ref[k].view(np.int32),
            np.asarray(getattr(res, k), np.float32).view(np.int32)), k
    done, _, _ = clock.busy_clock(ref["cols"], ref["pages"],
                                  ref["programs"][:, :, 4], ref["t_page"],
                                  ref["n_luns"], 4)
    assert np.array_equal(done, ref["completions"])


def test_legacy_copy_matches_the_program_legacy_device(replay):
    eng, res, lanes, _, _, _ = replay
    for lane, (s, policy) in enumerate(lanes):
        if policy != "traditional":
            continue
        prog = np.asarray(res.programs[lane])
        mine = legacy.replay(rgeo.FlashGeometry(**tiny.FLASH),
                             rgeo.ZoneGeometry(**tiny.ZONE), RSPECS[s], True,
                             14, prog)
        theirs = LegacyZNSDevice(eng.flash, eng.zone_geom, SPECS[s],
                                 max_active=14)
        for op, zone, n, flags in prog[:, :4].tolist():
            if op == 1 and theirs.zones[zone].state.name == "EMPTY":
                theirs._allocate_zone(zone)
            elif op == 2:
                theirs.zone_write(zone, n, host=bool(flags & 1))
            elif op == 3:
                theirs.zone_finish(zone)
            elif op == 4:
                theirs.zone_reset(zone)
        assert (mine.host_pages, mine.dummy_pages, mine.block_erases) == (
            theirs.host_pages, theirs.dummy_pages, theirs.block_erases)
        assert np.array_equal(mine.elem_wear, theirs.elem_wear)


@pytest.mark.parametrize("seed", [0, 1, 3000000031])
def test_recorder_copy_matches_the_program_recorder(seed):
    """The reference's KVBench recording equals the program's, row for
    row, at the zn540-zenfs configuration's own size."""
    import harness
    from repro.storage.compile import RecordingBackend, record_lsm
    from repro.storage.lsm import KVBenchConfig

    from reference.recorder import record_kvbench

    _, _, c, _ = harness.load_cell("zenfs.kvbench")
    classes = {"wal": 0, "flush": 1, "compact": 2}
    zp = (c["zone"]["parallelism"] * c["flash"]["pages_per_block"]
          * c["zone"]["n_segments"])
    rec = RecordingBackend(FlashGeometry(**c["flash"]), zone_pages=zp,
                           n_zones=c["zenfs"]["zones"],
                           max_active=c["max_active"], class_tenants=classes)
    record_lsm(rec, cfg=KVBenchConfig(**c["kvbench"], seed=seed),
               finish_threshold=c["zenfs"]["finish_threshold"])
    mine = record_kvbench(c["kvbench"], seed,
                          page_bytes=c["flash"]["page_bytes"], zone_pages=zp,
                          n_zones=c["zenfs"]["zones"],
                          max_active=c["max_active"],
                          finish_threshold=c["zenfs"]["finish_threshold"],
                          class_tenants=classes)
    assert np.array_equal(mine, rec.program())
