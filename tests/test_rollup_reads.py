"""Rollups read the final state from the device once per result.

``FleetResult.final`` fetches the final-state fields the rollups read
(element wear and the five counters) in one ``jax.device_get`` and
caches them; ``config_report`` (with its recovery keys),
``pooled_wear`` and ``storage.compile.lane_metrics`` roll up from that
host copy.  The values must equal those read straight from the device
states, field by field, as the rollups read them before; and one
result makes one read, counted as ``rollup.device_reads``.
"""

import numpy as np
import pytest

from repro import storage as S
from repro.core import engine as E
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.fleet import (N_TENANTS, Evaluator, FleetConfig, fleet_batch,
                         run_fleet, runner)
from repro.obs import Profiler

ND = 4
ENG = E.ZoneEngine(
    FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=32,
                  pages_per_block=4, page_bytes=4096),
    ZoneGeometry(4, 4), (SUPERBLOCK, BLOCK, vchunk(2)), max_active=6)

CASES = {
    "healthy": [
        FleetConfig("dlwa_pair", 4, 8, True, True, BLOCK),
        FleetConfig("dlwa_write", 2, 16, False, True, SUPERBLOCK,
                    alloc_policy="silent"),
        FleetConfig("dlwa_pair", 2, 8, True, False, vchunk(2))],
    "failed_member": [
        FleetConfig("dlwa_pair", 4, 8, True, True, SUPERBLOCK,
                    failure=(ND - 1, 0.5)),
        FleetConfig("dlwa_pair", 4, 8, True, True, BLOCK,
                    alloc_policy="silent", failure=(1, 0.3)),
        FleetConfig("dlwa_write", 2, 16, True, True, vchunk(2))],
}


def _dispatch(configs):
    programs, dyn, _, rebuilds = fleet_batch(ENG, configs, n_devices=ND)
    res = run_fleet(ENG, programs, dyn=dyn, n_tenants=N_TENANTS,
                    rebuilds=rebuilds)
    runner.assert_all_ok(res)
    return res


@pytest.mark.parametrize("case", sorted(CASES))
def test_rollups_equal_the_device_states(case):
    configs = CASES[case]
    res = _dispatch(configs)
    n = ENG.cfg.n_elements
    wear = np.asarray(res.states.elem_wear[:, :n], dtype=np.int64)
    host = np.asarray(res.states.host_pages)
    dummy = np.asarray(res.states.dummy_pages)
    for k, fc in enumerate(configs):
        lanes = np.arange(k * ND, (k + 1) * ND)
        w = wear[lanes][res.elem_mask[lanes]]
        pooled = res.pooled_wear(ENG, lanes)
        assert pooled.dtype == w.dtype and np.array_equal(pooled, w)
        rep = runner.config_report(res, ENG, lanes)
        mean_w = float(w.mean())
        assert rep["max_wear"] == float(w.max())
        assert rep["wear_cv"] == (float(w.std() / mean_w) if mean_w > 0
                                  else 0.0)
        if fc.failure is not None:
            assert rep["rebuild_pages"] > 0
            for d, lane in enumerate(lanes):
                h, pad = int(host[lane]), int(dummy[lane])
                assert rep[f"member{d}_dlwa"] == ((h + pad) / h if h
                                                  else 1.0)
        else:
            assert "member0_dlwa" not in rep
    assert np.array_equal(res.lane_wear(ENG), wear)
    for lane in range(len(res.programs)):
        assert S.lane_metrics(ENG, res, lane) == ENG.metrics(
            S.lane_state(res, lane))


def test_one_device_read_per_result():
    prof = Profiler()
    ev = Evaluator(ENG, n_devices=ND, profiler=prof)
    rows = ev.evaluate(CASES["healthy"] + CASES["failed_member"])
    assert len(rows) == 6
    assert prof.counters["rollup.device_reads"] == 1.0

    rec = S.RecordingBackend(ENG.flash, zone_pages=ENG.cfg.zone_pages,
                             n_zones=4, max_active=3)
    S.record_cache(rec, n_accesses=60, n_keys=12, seed=0,
                   capacity_zones=4, obj_pages=2)
    prof = Profiler()
    with prof.section("call"):
        res = S.replay_recorders(ENG, [rec, rec],
                                 dyns=[ENG.dyn(), ENG.dyn(spec=BLOCK)],
                                 pad_quantum=32, profiler=prof)
        for lane in range(2):
            S.lane_metrics(ENG, res, lane)
            res.pooled_wear(ENG, np.asarray([lane]))
            res.tenant_class_report(lanes=[lane])
    assert prof.counters["rollup.device_reads"] == 1.0
    # the read nests in the first rollup's span: 2 x 3 rollups
    assert prof.sections["fleet.rollup"]["calls"] == 6.0
