"""Tests for the evolutionary + successive-halving allocator search.

Four guarantees:

1. seeded determinism: same seed => identical generation history and
   Pareto archive (and a different seed actually explores differently);
2. elitist monotonicity: the best-so-far objective never increases
   across generations;
3. the halving schedule promotes exactly the top ``ceil(n/eta)`` of
   each rung's ranking -- and only those -- to the next rung, and only
   full-fidelity rows reach the archive/best;
4. the acceptance bar: on a seeded 2-tenant x 4-device fleet, evolve
   reaches an objective <= the best of a 32-config random search using
   <= half the random baseline's batched-evaluator budget (dispatches
   AND full-fidelity-equivalent evals).
"""

import math

import pytest

from repro.core import engine as E
from repro.core.elements import SUPERBLOCK
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.fleet import (Evaluator, EvolveParams, SearchSpace, evolve,
                         evolve_vs_random)

SPACE = SearchSpace(segments=(4, 2), chunks=(8, 16))   # 32 configs
PARAMS = EvolveParams(population=8, generations=3)


def tiny_engine():
    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=16, pages_per_block=4,
                          page_bytes=4096)
    return E.ZoneEngine(flash, ZoneGeometry(4, 4), SUPERBLOCK,
                        max_active=6)


@pytest.fixture(scope="module")
def eng():
    return tiny_engine()


@pytest.fixture(scope="module")
def result(eng):
    return evolve(eng, space=SPACE, params=PARAMS, seed=1, n_devices=4)


def test_space_codec_round_trips():
    for fc in SPACE.grid():
        assert SPACE.decode(SPACE.encode(fc)) == fc
    assert len(SPACE) == 32


def test_seeded_determinism(eng, result):
    rerun = evolve(eng, space=SPACE, params=PARAMS, seed=1, n_devices=4)
    assert rerun.history == result.history
    assert [r["config"] for r in rerun.archive] == \
        [r["config"] for r in result.archive]
    assert rerun.best == result.best
    # a different seed proposes a different generation 0
    other = evolve(eng, space=SPACE,
                   params=EvolveParams(population=8, generations=1),
                   seed=2, n_devices=4)
    assert other.history[0]["rungs"][0]["candidates"] != \
        result.history[0]["rungs"][0]["candidates"]


def test_best_objective_monotone_nonincreasing(result):
    curve = [h["best_so_far"] for h in result.history]
    assert all(b <= a + 1e-12 for a, b in zip(curve, curve[1:]))
    # best-so-far is the running minimum of the per-generation bests
    for i, h in enumerate(result.history):
        assert h["best_so_far"] == pytest.approx(
            min(g["best_of_gen"] for g in result.history[: i + 1]))


def test_halving_promotes_only_rung_survivors(result):
    eta = PARAMS.eta
    for h in result.history:
        rungs = h["rungs"]
        assert [r["fidelity"] for r in rungs] == \
            list(PARAMS.rung_fidelities)
        for lo, hi in zip(rungs, rungs[1:]):
            keep = max(1, math.ceil(len(lo["candidates"]) / eta))
            # survivors are exactly the rung ranking's top-keep slice,
            # and the next rung evaluates exactly those
            assert lo["survivors"] == lo["ranked"][:keep]
            assert hi["candidates"] == lo["survivors"]
        # ranking is a permutation of the rung's candidates
        for r in rungs:
            assert sorted(r["ranked"]) == sorted(r["candidates"])
    # only full-fidelity rows feed the archive and the best row
    assert all(r["fidelity"] == 1.0 for r in result.archive)
    assert result.best["fidelity"] == 1.0
    final = {n for h in result.history
             for n in h["rungs"][-1]["candidates"]}
    assert set(result.rows) == final
    assert {r["config"] for r in result.archive} <= final


def test_archive_is_nondominated(result):
    keys = ("dlwa", "wear_cv", "p99_latency_s")
    front = result.archive
    for a in front:
        for b in front:
            if a is b:
                continue
            dominates = (all(b[k] <= a[k] for k in keys)
                         and any(b[k] < a[k] for k in keys))
            assert not dominates, (a["config"], b["config"])


def test_empty_batch_does_not_skew_ledger(eng):
    """Regressions: ``stack_dyn([])`` died inside ``tree_map`` with an
    opaque error, and ``Evaluator.evaluate([])`` dispatched an empty
    batch while still counting a dispatch -- skewing the budget ledger
    evolve's halving decisions read."""
    with pytest.raises(ValueError, match="at least one DynConfig"):
        E.stack_dyn([])
    ev = Evaluator(eng, n_devices=4)
    assert ev.evaluate([]) == []
    assert ev.evaluate([], fidelity=0.25) == []
    assert (ev.n_dispatches, ev.n_evals, ev.lane_ops) == (0, 0.0, 0)
    # a real batch afterwards counts exactly once
    ev.evaluate(SPACE.grid()[:2])
    assert (ev.n_dispatches, ev.n_evals) == (1, 2.0)


def test_evaluator_ledger_and_fidelity(eng):
    ev = Evaluator(eng, n_devices=4)
    configs = SPACE.grid()[:4]
    full = ev.evaluate(configs)
    assert ev.n_dispatches == 1 and ev.n_evals == 4.0
    cheap = ev.evaluate(configs, fidelity=0.25)
    assert ev.n_dispatches == 2 and ev.n_evals == 5.0
    assert ev.lane_ops > 0
    # truncated rungs really are cheaper: fewer real ops dispatched
    assert sum(r["host_pages"] for r in cheap) < \
        sum(r["host_pages"] for r in full)
    for r in cheap:
        assert r["fidelity"] == 0.25


def test_acceptance_evolve_beats_random_at_half_budget(eng):
    """ISSUE 4 acceptance: seeded 2-tenant x 4-device fleet -- evolve
    reaches an objective <= the best of 32-config random search with
    <= half the batched evaluator dispatches and <= half the
    full-fidelity-equivalent evals."""
    rep = evolve_vs_random(eng, space=SPACE, params=PARAMS,
                           random_n=32, seed=0, n_devices=4)
    assert rep["random"]["n_configs"] == 32.0
    assert rep["evolve"]["reached_target"]
    assert rep["evolve"]["best_objective"] <= \
        rep["random"]["best_objective"] + 1e-12
    assert rep["evolve"]["n_dispatches"] <= \
        rep["random"]["n_dispatches"] / 2
    assert rep["evolve"]["n_evals"] <= rep["random"]["n_evals"] / 2
