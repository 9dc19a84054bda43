"""repro.check: verifier-vs-engine differentials, sanitizer, lint.

Three surfaces, each with its own hard guarantee:

1. the static verifier's predicted ok-mask must be *bit-identical* to
   ``trace.ok`` from the batched engine on fuzzed programs, across
   every element spec and both allocation policies (dyn overrides
   included) -- the verifier is a numpy transliteration of the engine
   state machine, and any semantic drift must fail here;
2. the DeviceState sanitizer accepts every state a legal dispatch
   produces and rejects hand-corrupted pytrees, while adding zero jit
   compilations (it is numpy on fetched values);
3. the AST lint recognises each JAX-pitfall rule on minimal sources,
   honours the ``# lint: ok`` pragma, and the repo's own tree is clean
   (the CI gate, mirrored here so tier-1 catches regressions first).

``REPRO_SANITIZE=1`` (the CI sanitizer job) additionally audits every
final state the fuzz differentials produce.
"""

import os
import pathlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.check import (ERR_ACTIVE_LIMIT, ERR_ALLOC_INFEASIBLE, ERR_FULL,
                         ERR_OVERFLOW, ERR_UNMAPPED_READ, SanitizerError,
                         assert_state, assert_states, check_state,
                         check_states, explain_op, validate_rows,
                         verify_program, verify_programs)
from repro.check.lint import (Finding, bench_artifacts, lint_source,
                              lint_tree)
from repro.core import engine as E
from repro.core.device import ZNSDevice
from repro.core.elements import BLOCK, FIXED, SUPERBLOCK, hchunk, vchunk
from repro.core.engine import ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry

SANITIZE_ALL = os.environ.get("REPRO_SANITIZE") == "1"
SPECS = [BLOCK, vchunk(2), hchunk(2), SUPERBLOCK, FIXED]
N_OPS = 24  # fixed program length -> one compiled entry per engine


def tiny_flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=8,
                         pages_per_block=4, page_bytes=4096)


_ENGINES = {}


def tiny_engine(spec) -> ZoneEngine:
    if spec.name not in _ENGINES:
        _ENGINES[spec.name] = ZoneEngine(
            tiny_flash(), ZoneGeometry(parallelism=4, n_segments=2),
            spec, max_active=3)
    return _ENGINES[spec.name]


def random_program(rng, eng) -> np.ndarray:
    """A fuzz program exercising every op, out-of-range zones (clipped
    by the engine), overflow page counts, and non-host writes."""
    zp = eng.cfg.zone_pages
    rows = np.zeros((N_OPS, 4), np.int32)
    rows[:, 0] = rng.integers(E.OP_NOP, E.OP_READ + 1, N_OPS)
    rows[:, 1] = rng.integers(-1, eng.cfg.n_zones + 2, N_OPS)
    rows[:, 2] = rng.integers(0, zp + 3, N_OPS)
    rows[:, 3] = rng.integers(0, 2, N_OPS)
    return rows


def fuzz_dyn(rng, eng, policy: str):
    """Random-but-valid dyn overrides (the axes make_dyn accepts)."""
    kw = {"alloc_policy": policy,
          "max_active": int(rng.choice([2, 3])),
          "wear_aware": bool(rng.integers(0, 2))}
    if eng.spec.kind.name != "FIXED" and rng.integers(0, 2):
        kw["zone_pages"] = eng.cfg.zone_pages // 2
    if policy == "silent" and rng.integers(0, 2):
        kw["wear_bound"] = int(rng.choice([0, 1]))
    return eng.dyn(**kw)


def run_and_compare(eng, prog, dyn, ctx=""):
    state, trace = eng.run(eng.init_state(), prog, dyn)
    rep = verify_program(eng.cfg, prog, dyn)
    got = np.asarray(trace.ok).astype(bool)
    assert np.array_equal(rep.ok, got), (
        f"ok-mask mismatch {ctx}: first diff at op "
        f"{int(np.argmax(rep.ok != got))}; predicted "
        f"{rep.ok.tolist()} engine {got.tolist()}")
    if SANITIZE_ALL:
        assert_state(eng.cfg, state, dyn, where=f"fuzz final state {ctx}",
                     metrics=eng.metrics(state))
    return state, rep


# --------------------------------------------------------------------- #
# 1. verifier ok-mask == engine trace.ok (the differential guarantee)
# --------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, len(SPECS) - 1))
def test_verifier_matches_engine_traditional(seed, spec_i):
    eng = tiny_engine(SPECS[spec_i])
    rng = np.random.default_rng(seed)
    dyn = fuzz_dyn(rng, eng, "traditional")
    run_and_compare(eng, random_program(rng, eng), dyn,
                    ctx=f"seed={seed} spec={SPECS[spec_i].name}")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, len(SPECS) - 2))
def test_verifier_matches_engine_silent(seed, spec_i):
    # FIXED is excluded: make_dyn rejects silent-on-FIXED eagerly (the
    # verifier's conflict report covers the smuggled-dyn case)
    eng = tiny_engine(SPECS[spec_i])
    rng = np.random.default_rng(seed)
    dyn = fuzz_dyn(rng, eng, "silent")
    run_and_compare(eng, random_program(rng, eng), dyn,
                    ctx=f"seed={seed} spec={SPECS[spec_i].name} silent")


def test_verifier_matches_engine_stacked_lanes():
    """verify_programs over a heterogeneous stacked-dyn batch lane-for-
    lane against one run_programs dispatch (plus the lane sanitizer)."""
    eng = tiny_engine(BLOCK)
    rng = np.random.default_rng(7)
    programs = np.stack([random_program(rng, eng) for _ in range(4)])
    dyns = [eng.dyn(alloc_policy="traditional"),
            eng.dyn(alloc_policy="silent", wear_bound=1),
            eng.dyn(zone_pages=eng.cfg.zone_pages // 2, max_active=2),
            eng.dyn(alloc_policy="silent")]
    dyn = E.stack_dyn(dyns)
    states, trace = eng.run_batch(eng.init_state(), programs, dyn)
    reports = verify_programs(eng.cfg, programs, dyn)
    ok = np.asarray(trace.ok).astype(bool)
    for k, rep in enumerate(reports):
        assert np.array_equal(rep.ok, ok[k]), f"lane {k}"
    assert_states(eng.cfg, states, dyn, where="stacked fuzz states")
    assert check_states(eng.cfg, states, dyn) == [[], [], [], []]


# --------------------------------------------------------------------- #
# 2. verdict classification: error classes + the shim's exact messages
# --------------------------------------------------------------------- #
def shim_error(dev_ops):
    """Drive the ZNSDevice shim; return str of the first RuntimeError."""
    dev = ZNSDevice(tiny_flash(), ZoneGeometry(parallelism=4, n_segments=2),
                    BLOCK, max_active=3)
    try:
        for op, z, n, host in dev_ops:
            if op == E.OP_WRITE:
                dev.zone_write(z, n, host=bool(host))
            elif op == E.OP_FINISH:
                dev.zone_finish(z)
            elif op == E.OP_READ:
                dev.zone_read(z, np.arange(max(n, 1)))
    except RuntimeError as exc:
        return str(exc)
    return None


def test_verdict_full_matches_shim():
    eng = tiny_engine(BLOCK)
    zp = eng.cfg.zone_pages
    prog = np.asarray([(E.OP_WRITE, 0, zp, E.F_HOST),
                       (E.OP_WRITE, 0, 1, E.F_HOST)], np.int32)
    rep = verify_program(eng.cfg, prog)
    v = rep.first_failure()
    assert v.index == 1 and v.error == ERR_FULL
    assert v.message == shim_error(
        [(E.OP_WRITE, 0, zp, 1), (E.OP_WRITE, 0, 1, 1)])
    assert "FULL zone 0" in str(v.message)


def test_verdict_overflow_matches_shim():
    eng = tiny_engine(BLOCK)
    zp = eng.cfg.zone_pages
    prog = np.asarray([(E.OP_WRITE, 1, zp + 1, E.F_HOST)], np.int32)
    v = verify_program(eng.cfg, prog).first_failure()
    assert v.error == ERR_OVERFLOW
    assert v.message == shim_error([(E.OP_WRITE, 1, zp + 1, 1)])


def test_verdict_active_limit_matches_shim():
    eng = tiny_engine(BLOCK)
    ops = [(E.OP_WRITE, z, 1, 1) for z in range(4)]  # max_active = 3
    prog = np.asarray([(E.OP_WRITE, z, 1, E.F_HOST) for z in range(4)],
                      np.int32)
    v = verify_program(eng.cfg, prog).first_failure()
    assert v.index == 3 and v.error == ERR_ACTIVE_LIMIT
    assert v.message == shim_error(ops)


def test_verdict_unmapped_read_is_advisory():
    """Engine READs never fail; the verifier reports the control-plane
    error (what the shim would raise) as an advisory."""
    eng = tiny_engine(BLOCK)
    prog = np.asarray([(E.OP_READ, 2, 4, 0)], np.int32)
    rep = run_and_compare(eng, prog, None, ctx="unmapped read")[1]
    assert rep.all_ok and len(rep.advisories) == 1
    adv = rep.advisories[0]
    assert adv.error == ERR_UNMAPPED_READ
    assert adv.message == shim_error([(E.OP_READ, 2, 4, 0)])


def test_verdict_alloc_infeasible_wear_bound():
    """A silent lane whose only free elements sit beyond wear_bound of
    the minimum: alloc is infeasible (with the shim's message) and the
    op lands in the wear-bound-blocked report (unbounded would fit)."""
    from repro.check.verifier import _Dv, _Model
    eng = tiny_engine(BLOCK)
    dv = _Dv(E.dyn_values(eng.cfg, eng.dyn(alloc_policy="silent",
                                           wear_bound=0)))
    m = _Model(eng.cfg, dv)
    m.wear[:] = 5
    m.wear[0] = 0  # single least-worn element; the rest out of bound
    ok, err, msg = m._alloc(0, 0)
    assert not ok and err == ERR_ALLOC_INFEASIBLE
    assert msg == f"no free storage elements for zone 0 ({BLOCK.name})"
    assert m.wear_bound_blocked == [0]


def test_explain_op_walks_prefix():
    eng = tiny_engine(BLOCK)
    zp = eng.cfg.zone_pages
    prog = np.asarray([(E.OP_WRITE, 0, zp, E.F_HOST),
                       (E.OP_WRITE, 0, 1, E.F_HOST)], np.int32)
    v = explain_op(eng.cfg, prog, 1)
    assert not v.ok and v.error == ERR_FULL and v.op_name == "WRITE"
    assert explain_op(eng.cfg, prog, 0).ok


# --------------------------------------------------------------------- #
# 3. report analyses: dummy sites, DLWA bound, peak active, conflicts
# --------------------------------------------------------------------- #
def test_report_dummy_sites_and_dlwa_bound():
    eng = tiny_engine(BLOCK)
    prog = np.asarray([(E.OP_WRITE, 0, 6, E.F_HOST),
                       (E.OP_FINISH, 0, 0, 0),   # pads partial elements
                       (E.OP_WRITE, 1, 3, 0),    # non-host (dummy) write
                       (E.OP_FINISH, 1, 0, 0)], np.int32)
    state, rep = run_and_compare(eng, prog, None, ctx="dummy sites")
    assert rep.all_ok
    # every superfluous-write source is a site: the two FINISH paddings
    # plus the explicit non-host write, and the site pages sum to the
    # exact dummy-page counter the engine reports
    assert sorted(i for i, _, _ in rep.dummy_sites) == [1, 2, 3]
    assert (2, 1, 3) in rep.dummy_sites
    assert sum(p for _, _, p in rep.dummy_sites) == rep.dummy_pages
    met = eng.metrics(state)
    assert rep.host_pages == int(met["host_pages"])
    assert rep.dummy_pages == int(met["dummy_pages"])
    assert rep.dummy_pages > 3  # the FINISH pads really contributed
    assert rep.dlwa_lower_bound == pytest.approx(met["dlwa"])
    assert rep.peak_active == 1  # each zone sealed before the next opens


def test_report_peak_active_pressure():
    eng = tiny_engine(BLOCK)
    prog = np.asarray([(E.OP_WRITE, z, 1, E.F_HOST) for z in range(3)]
                      + [(E.OP_FINISH, z, 0, 0) for z in range(3)],
                      np.int32)
    rep = run_and_compare(eng, prog, None, ctx="peak active")[1]
    assert rep.all_ok and rep.peak_active == 3


def test_report_conflicts_on_smuggled_dyn():
    """make_dyn rejects these eagerly; hand-stacked DynConfigs can
    smuggle them past it -- the verifier reports without walking ops."""
    fixed = tiny_engine(FIXED)
    dyn = fixed.dyn()._replace(alloc_policy=E.POLICY_SILENT)
    rep = verify_program(fixed.cfg, np.zeros((1, 4), np.int32), dyn)
    assert any("FIXED" in c for c in rep.conflicts)
    blk = tiny_engine(BLOCK)
    dyn = blk.dyn()._replace(wear_bound=-2)
    rep = verify_program(blk.cfg, np.zeros((1, 4), np.int32), dyn)
    assert any("wear_bound" in c for c in rep.conflicts)


# --------------------------------------------------------------------- #
# 4. sanitizer: accepts engine states, rejects corrupted pytrees
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("spec_i", range(len(SPECS)))
def test_sanitizer_accepts_engine_states(spec_i):
    eng = tiny_engine(SPECS[spec_i])
    rng = np.random.default_rng(11 + spec_i)
    state, _ = eng.run(eng.init_state(), random_program(rng, eng))
    assert check_state(eng.cfg, state, metrics=eng.metrics(state)) == []


def open_zone_state(eng):
    zp = eng.cfg.zone_pages
    prog = np.asarray([(E.OP_WRITE, 0, zp // 2, E.F_HOST),
                       (E.OP_WRITE, 1, zp, E.F_HOST)], np.int32)
    state, trace = eng.run(eng.init_state(), prog)
    assert bool(np.asarray(trace.ok).all())
    return state


def test_sanitizer_rejects_corrupted_states():
    eng = tiny_engine(BLOCK)
    state = open_zone_state(eng)

    wp = np.asarray(state.zone_wp).copy()
    wp[0] = eng.cfg.zone_pages + 7
    v = check_state(eng.cfg, state._replace(zone_wp=wp))
    assert any("wp" in s and "outside" in s for s in v)

    ze = np.asarray(state.zone_elems).copy()
    ze[2] = ze[0]  # element committed to two zones
    v = check_state(eng.cfg, state._replace(zone_elems=ze))
    assert any("disjointness" in s for s in v)

    na = np.asarray(state.n_active).copy()
    v = check_state(eng.cfg, state._replace(n_active=na + 1))
    assert any("OPEN" in s for s in v)

    av = np.asarray(state.elem_avail).copy()
    av[0] = 9
    v = check_state(eng.cfg, state._replace(elem_avail=av))
    assert any("avail code 9" in s for s in v)

    v = check_state(eng.cfg, state, metrics={"dlwa": 123.0})
    assert any("metrics['dlwa']" in s for s in v)

    with pytest.raises(SanitizerError, match="corrupt demo"):
        assert_state(eng.cfg, state._replace(zone_wp=wp),
                     where="corrupt demo")
    try:
        assert_state(eng.cfg, state._replace(zone_wp=wp))
    except SanitizerError as exc:
        assert exc.violations  # the full list rides on the exception


def test_sanitizer_scratch_wear_and_negative_counters():
    eng = tiny_engine(BLOCK)
    state = open_zone_state(eng)
    w = np.asarray(state.elem_wear).copy()
    w[-1] = 3  # the masked-scatter scratch slot must stay zero
    v = check_state(eng.cfg, state._replace(elem_wear=w))
    assert any("scratch" in s for s in v)
    hp = np.asarray(state.host_pages) * 0 - 4
    v = check_state(eng.cfg, state._replace(host_pages=hp),
                    check_wear=False)
    assert any("negative page counters" in s for s in v)


# --------------------------------------------------------------------- #
# 5. malformed-row pre-checks + the pipelines that call them
# --------------------------------------------------------------------- #
def test_validate_rows_rejects_malformed():
    good = np.asarray([[E.OP_WRITE, 0, 4, 1, 0]], np.int32)
    assert validate_rows(good, n_tenants=1).dtype == np.int32

    bad = good.copy()
    bad[0, 0] = 9
    with pytest.raises(ValueError, match="op code 9"):
        validate_rows(bad)
    bad = good.copy()
    bad[0, 1] = -3
    with pytest.raises(ValueError, match="negative zone"):
        validate_rows(bad, where="wl")
    bad = good.copy()
    bad[0, 2] = -1
    with pytest.raises(ValueError, match="negative page count"):
        validate_rows(bad)
    bad = good.copy()
    bad[0, 4] = 5
    with pytest.raises(ValueError, match="tenant"):
        validate_rows(bad, n_tenants=2)
    # NOP rows are padding: exempt from the zone/page/tenant bounds
    nop = np.asarray([[E.OP_NOP, -5, -5, 0, 99]], np.int32)
    validate_rows(nop, n_tenants=2)
    with pytest.raises(ValueError, match="columns"):
        validate_rows(np.zeros((2, 3), np.int32))
    with pytest.raises(ValueError, match="shape"):
        validate_rows(np.zeros((2,), np.int32))


def test_replay_recorders_rejects_malformed_rows():
    import repro.storage as S

    class BadRecorder:
        def program(self):
            return np.asarray([[E.OP_WRITE, -1, 4, 1, 0]], np.int32)

    eng = tiny_engine(BLOCK)
    with pytest.raises(ValueError, match=r"recorder 0 .*negative zone"):
        S.replay_recorders(eng, [BadRecorder()])


# --------------------------------------------------------------------- #
# 6. sanitize= threading adds zero jit compilations
# --------------------------------------------------------------------- #
def test_sanitize_adds_zero_recompiles():
    from repro.obs.profile import RecompileCounter
    eng = tiny_engine(BLOCK)
    rng = np.random.default_rng(3)
    programs = np.stack([random_program(rng, eng) for _ in range(2)])
    dyn = E.stack_dyn([eng.dyn(), eng.dyn(alloc_policy="silent")])
    counter = RecompileCounter(run_programs=E.run_programs)
    eng.run_batch(eng.init_state(), programs, dyn)  # warm/compile
    before = counter.counts()
    states, _ = eng.run_batch(eng.init_state(), programs, dyn)
    assert_states(eng.cfg, states, dyn, where="recompile probe")
    assert sum(counter.delta(before).values()) == 0


def test_evaluator_sanitize_flag():
    """Evaluator(sanitize=True) audits every dispatch's states without
    changing results or growing the jit cache across generations."""
    from repro.fleet import Evaluator, grid_space
    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=16, pages_per_block=4,
                          page_bytes=4096)
    eng = ZoneEngine(flash, ZoneGeometry(4, 4), SUPERBLOCK, max_active=6)
    configs = grid_space(segments=(4, 2), chunks=(8,),
                         parities=(False,), wear=(True,))[:2]
    ev = Evaluator(eng, n_devices=2, sanitize=True)
    rows = ev.evaluate(configs)
    assert len(rows) == len(configs)
    cache1 = ev.jit_cache()["run_programs"]
    ev.evaluate(configs)
    assert ev.jit_cache()["run_programs"] == cache1


# --------------------------------------------------------------------- #
# 7. assert_all_ok: verifier-routed rich exceptions
# --------------------------------------------------------------------- #
def test_assert_all_ok_names_error_class():
    from repro.fleet.runner import run_fleet
    eng = tiny_engine(BLOCK)
    zp = eng.cfg.zone_pages
    rows = np.zeros((2, 4, 5), np.int32)
    rows[0, 0] = (E.OP_WRITE, 0, zp, E.F_HOST, 0)
    rows[1, 0] = (E.OP_WRITE, 0, zp + 1, E.F_HOST, 0)  # overflow
    res = run_fleet(eng, rows)
    with pytest.raises(AssertionError) as exc:
        from repro.fleet.runner import assert_all_ok
        assert_all_ok(res)
    msg = str(exc.value)
    assert "predicted error class" in msg
    assert ERR_OVERFLOW in msg and "WRITE" in msg


# --------------------------------------------------------------------- #
# 8. lint rules
# --------------------------------------------------------------------- #
def rules(src, **kw):
    return [f.rule for f in lint_source(src, "mod.py", **kw)]


def test_lint_dispatch_in_loop():
    src = "for p in programs:\n    run_programs(cfg, s, p)\n"
    assert rules(src) == ["dispatch-in-loop"]
    assert rules("run_programs(cfg, s, batch)\n") == []
    hoisted = ("def f(cfg, batch):\n"
               "    for p in batch:\n"
               "        rows.append(p)\n"
               "    return run_programs(cfg, s, rows)\n")
    assert rules(hoisted) == []


def test_lint_vmap_over_scan():
    assert rules("jax.vmap(run_program)(xs)\n") == ["vmap-over-scan"]
    assert rules("jax.vmap(lambda s: apply_op(cfg, s, r))(xs)\n") \
        == ["vmap-over-scan"]
    assert rules("jax.vmap(other_fn)(xs)\n") == []


def test_lint_jit_needs_static():
    src = "@jax.jit\ndef f(cfg, x):\n    return x\n"
    assert rules(src) == ["jit-needs-static"]
    src = ("@functools.partial(jax.jit, static_argnames=('cfg',))\n"
           "def f(cfg, x):\n    return x\n")
    assert rules(src) == []
    assert rules("@jax.jit\ndef g(x):\n    return x\n") == []


def test_lint_bench_schema():
    names = {"BENCH_paper.json"}
    src = "p = root / 'BENCH_stale.json'\n"
    assert rules(src, bench_names=names) == ["bench-schema"]
    assert rules("p = root / 'BENCH_paper.json'\n",
                 bench_names=names) == []
    # hard-coded schema_version comparisons only flagged in files that
    # reference bench artifacts (the Perfetto export's own schema with
    # no bench mention stays clean)
    versioned = "ok = artifact['schema_version'] == 5\n"
    assert rules(versioned, bench_names=names) == []
    assert rules("# BENCH_paper.json reader\n" + versioned,
                 bench_names=names) == ["bench-schema"]


def test_bench_artifacts_reads_the_paper_writer():
    """The rule's artifact names come from the one writer; an empty set
    would switch the artifact-name half of ``bench-schema`` off."""
    root = pathlib.Path(__file__).resolve().parents[1]
    assert bench_artifacts(root) == {"BENCH_paper.json"}


def test_lint_pragma_suppresses():
    src = "for p in ps:\n    run_programs(cfg, s, p)  # lint: ok\n"
    assert rules(src) == []


def test_lint_reports_syntax_errors():
    out = lint_source("def broken(:\n", "mod.py")
    assert out and out[0].rule == "syntax"
    assert isinstance(out[0], Finding) and "mod.py" in str(out[0])


def test_repo_tree_is_lint_clean():
    root = pathlib.Path(__file__).resolve().parents[1]
    findings = lint_tree(root)
    assert findings == [], "\n".join(str(f) for f in findings)
