"""``chip_smoke.py`` on the CPU: its refusal, its phases, its checks.

The smoke's phases run here at a tiny geometry (the chip runs them at
zn540), so the references it holds the device to -- the numpy verifier,
the legacy replay, the numpy float32 busy clock, the sanitizer -- are
exercised on every test run, and each check is shown to catch a
corrupted result.
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.engine import ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def eng(smoke):
    # 16 zones of 4 segments: room for two 6-zone lsm windows
    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=64, pages_per_block=16,
                          page_bytes=16384)
    return ZoneEngine(flash, ZoneGeometry(4, 4), smoke.SPECS,
                      max_active=14)


def test_smoke_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


@pytest.mark.parametrize("phase", ["fleet", "lsm", "ckpt", "cache"])
def test_smoke_phase_matches_its_references(smoke, eng, phase):
    device = jax.devices()[0]
    if phase == "fleet":
        line = smoke.fleet_phase(eng, device, segments=(4, 2),
                                 chunks=(32, 64))
        assert line["lanes"] == 96 * 4 == line["checks"]["ok_lanes"]
        assert line["checks"]["evaluator_rows"] == 96
    else:
        line = smoke.workload_phase(eng, device, phase,
                                    smoke.WORKLOADS.index(phase))
        assert line["checks"]["ok_lanes"] == 4
    assert line["recompiles_warm"] == 0
    assert line["checks"]["legacy_lanes"] >= 4
    assert line["lane_ops"] == line["lanes"] * line["ops"]


@pytest.fixture(scope="module")
def lsm_result(eng):
    import repro.storage as storage
    res, _ = storage.run_workload(eng, "lsm")
    return res


def _bump(a, lane, op):
    """One float32 ulp up at (lane, op) of a copy."""
    a = np.array(a, np.float32)
    a[lane, op] = np.nextafter(a[lane, op], np.float32(np.inf))
    return a


@pytest.mark.parametrize("corrupt", ["completion", "latency", "ok",
                                     "wear"])
def test_smoke_checks_catch_a_corrupted_result(smoke, eng, lsm_result,
                                               corrupt):
    res = lsm_result
    lane, op = 1, int(np.flatnonzero(res.pages[1] > 0)[3])
    if corrupt == "completion":
        bad = dataclasses.replace(
            res, completions=_bump(res.completions, lane, op))
        check = lambda: smoke.check_clock(bad, eng.flash)  # noqa: E731
    elif corrupt == "latency":
        bad = dataclasses.replace(
            res, latencies=_bump(res.latencies, lane, op))
        check = lambda: smoke.check_clock(bad, eng.flash)  # noqa: E731
    elif corrupt == "ok":
        ok = res.ok.copy()
        ok[lane, op] = ~ok[lane, op]
        bad = dataclasses.replace(res, ok=ok)
        check = lambda: smoke.check_ok(bad)  # noqa: E731
    else:
        wear = np.array(res.states.elem_wear)
        wear[lane, 0] += 1
        bad = dataclasses.replace(
            res, states=res.states._replace(elem_wear=wear))
        spec = eng.spec
        check = lambda: smoke.check_lanes(  # noqa: E731
            eng, bad, [(lane, spec, eng.zone_geom.n_segments,
                        eng.cfg.wear_aware, "traditional")])
    smoke.check_clock(res, eng.flash)
    smoke.check_ok(res)
    with pytest.raises(smoke.CheckFailed, match=f"lane {lane}"):
        check()


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-set", "env-unset"])
def test_compile_cache_dir_follows_the_environment(smoke, monkeypatch,
                                                   tmp_path, from_env):
    from benchmarks.common import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(REPO / ".jax_cache")
            assert use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
