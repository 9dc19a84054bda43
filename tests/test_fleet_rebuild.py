"""A member failure and its rebuild on the fleet search path.

``FleetConfig(failure=(member, at))`` makes the fleet builder fail one
member of a parity-on array before row ``int(at * n_rows)`` of the
merged tenant stream and rebuild it under the rest
(:func:`repro.fleet.tenants.stripe_rebuild`).  The oracles are the
array layer's: :class:`repro.array.ArrayEngine` driven through the same
logical commands with ``fail_device`` + ``rebuild_device`` (its rows and
final states bit for bit), and the object :class:`repro.array.ZNSArray`
over per-op legacy members (per-member host/padding pages everywhere,
every report key where the failure comes after the last row).  Also:
the ordering rule of the merge, the clock of a rebuild (nothing after
the failure starts before it, a rebuilt chunk waits for its survivor
reads), the healthy path unchanged against digests taken before
failures existed, the timing's tenant count, parity-off refusal, and
the spans and counters.
"""

import hashlib
import json
import pathlib
import random
import sys

import numpy as np
import pytest

from repro.array import (ArrayEngine, ArrayGeometry, merge_rebuild,
                         plan_rebuild, run_array_batch)
from repro.array.engine import _legacy_array
from repro.core import engine as E
from repro.core import timing
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.fleet import (N_TENANTS, Evaluator, FleetConfig, SearchSpace,
                         build_fleet_batch, fleet_batch, grid_space,
                         random_space, run_fleet, search)
from repro.fleet.tenants import stripe_program, stripe_rebuild
from repro.obs import Profiler, count

REPO = pathlib.Path(__file__).resolve().parents[1]
SPECS = (SUPERBLOCK, BLOCK, vchunk(2))
ND = 4
CHUNK = 8
MIX = "test_rebuild_pair"
REBUILD_TAG = N_TENANTS + 1
COUNTERS = ("build.rebuild_rows", "build.rebuild_read_pages",
            "build.rebuild_held_rows")


def flash():
    return FlashGeometry(n_channels=4, ways_per_channel=1,
                         blocks_per_lun=32, pages_per_block=4,
                         page_bytes=4096)


@pytest.fixture(scope="module")
def eng():
    return E.ZoneEngine(flash(), ZoneGeometry(4, 4), SPECS, max_active=6)


def tenant_stream(seed: int, cap: int, zones, n_rows: int):
    """One tenant's legal logical rows over its own superzones: writes,
    FINISH of open zones that are not full, and RESETs (churn)."""
    rng = random.Random(seed)
    wp = {z: 0 for z in zones}          # None once FULL
    rows = []
    while len(rows) < n_rows:
        z = rng.choice(zones)
        verb = rng.choice(["write"] * 5 + ["finish", "reset"])
        if verb == "write" and wp[z] is not None and wp[z] < cap:
            n = min(rng.randrange(1, cap // 3), cap - wp[z])
            rows.append((E.OP_WRITE, z, n, E.F_HOST))
            wp[z] += n
            if wp[z] == cap:
                wp[z] = None
        elif verb == "finish" and wp[z]:
            rows.append((E.OP_FINISH, z, 0, 0))
            wp[z] = None
        elif verb == "reset" and wp[z] != 0:
            rows.append((E.OP_RESET, z, 0, 0))
            wp[z] = 0
    return E.encode_program(rows)


def _mix(eng, cap):
    return [tenant_stream(11, cap, [0, 1, 2], 40),
            tenant_stream(12, cap, [3, 4, 5], 40)]


@pytest.fixture
def mix(monkeypatch):
    monkeypatch.setitem(search.MIXES, MIX, _mix)
    return MIX


def _config(spec, policy, at, parity=True):
    return FleetConfig(MIX, 4, CHUNK, parity, True, spec,
                       alloc_policy=policy, failure=(ND - 1, at))


def _oracle_engine(eng, fc, merged, at_row):
    """ArrayEngine driven by the merged logical rows, failing and
    rebuilding the member at ``at_row``; each lane's rebuild and later
    rows then merged as the fleet path merges them."""
    member = fc.failure[0]
    seg = eng.zone_geom.parallelism * eng.flash.pages_per_block
    arr = ArrayEngine(eng, ArrayGeometry(ND, CHUNK, True),
                      member_specs=(fc.spec,) * ND,
                      zone_pages=seg * fc.n_segments,
                      wear_aware=fc.wear_aware,
                      alloc_policy=fc.alloc_policy, n_tenants=N_TENANTS)

    def drive(rows):
        for op, z, n, flags, t in rows.tolist():
            if op == E.OP_WRITE:
                arr.zone_write(z, n, host=bool(flags & E.F_HOST), tenant=t)
            elif op == E.OP_FINISH:
                arr.zone_finish(z, tenant=t)
            elif op == E.OP_RESET:
                arr.zone_reset(z, tenant=t)

    drive(merged[:at_row])
    marks = [len(r) for r in arr._rows]
    arr.fail_device(member)
    arr.rebuild_device(member)
    marks[member] = 0
    rebuilt = [len(r) for r in arr._rows]
    drive(merged[at_row:])
    sequential = [list(r) for r in arr._rows]
    for d, rows in enumerate(arr._rows):
        arr._rows[d] = rows[:marks[d]] + merge_rebuild(
            rows[rebuilt[d]:], rows[marks[d]:rebuilt[d]],
            replacement=d == member)[0]
    arr._dirty = True
    return arr, sequential


def _object_array(eng, fc, merged, at_row):
    """The object ZNSArray over legacy members, commands in sequence."""
    seg = eng.zone_geom.parallelism * eng.flash.pages_per_block
    arr = _legacy_array(eng.flash, ZoneGeometry(4, fc.n_segments),
                        ArrayGeometry(ND, CHUNK, True), (fc.spec,) * ND,
                        max_active=6)
    assert arr.dev_zone_pages == seg * fc.n_segments
    for d in arr.devices:
        d.wear_aware = fc.wear_aware
    for i, (op, z, n, flags, _) in enumerate(merged.tolist()):
        if i == at_row:
            arr.fail_device(fc.failure[0])
            arr.rebuild_device(fc.failure[0])
        if op == E.OP_WRITE:
            arr.zone_write(z, n, host=bool(flags & E.F_HOST))
        elif op == E.OP_FINISH:
            arr.zone_finish(z)
        elif op == E.OP_RESET:
            arr.zone_reset(z)
    if at_row >= len(merged):
        arr.fail_device(fc.failure[0])
        arr.rebuild_device(fc.failure[0])
    return arr


@pytest.mark.parametrize("where", ["first", "mid", "last", "after"])
@pytest.mark.parametrize("policy", ["traditional", "silent"])
@pytest.mark.parametrize("spec", [SUPERBLOCK, BLOCK],
                         ids=lambda s: s.name)
def test_fleet_rebuild_matches_the_array_oracles(eng, mix, spec, policy,
                                                 where):
    probe = _config(spec, policy, 0.0)
    n = len(build_fleet_batch(eng, [probe], n_devices=ND)[2][0])
    at_row = {"first": 0, "mid": n // 2, "last": n - 1, "after": n}[where]
    fc = _config(spec, policy, at_row / n)
    programs, dyn, merged, rebuilds = fleet_batch(
        eng, [fc], n_devices=ND, pad_quantum=64)
    merged = merged[0]
    assert int(fc.failure[1] * len(merged)) == at_row
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                    rebuilds=rebuilds)

    arr, sequential = _oracle_engine(eng, fc, merged, at_row)
    want = arr.member_programs()
    for d in range(ND):
        got = programs[d][programs[d, :, 0] != E.OP_NOP]
        np.testing.assert_array_equal(got, want[d])
        # the merge only interleaves: each stream keeps its order
        seq = np.asarray(sequential[d], dtype=np.int32).reshape(-1, 5)
        for rebuild in (True, False):
            np.testing.assert_array_equal(
                got[(got[:, 4] == REBUILD_TAG) == rebuild],
                seq[(seq[:, 4] == REBUILD_TAG) == rebuild])
    (ares,) = run_array_batch([arr], pad_quantum=64)
    for f in ("elem_wear", "elem_avail", "elem_pages", "elem_zone",
              "zone_state", "zone_wp", "zone_host_wp", "zone_elems",
              "zone_cols", "rr_next", "n_active", "host_pages",
              "dummy_pages", "block_erases"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res.states, f)),
            np.asarray(getattr(ares.states, f)), f)
    assert res.ok[programs[:, :, 0] != E.OP_NOP].all()

    obj = _object_array(eng, fc, merged, at_row)
    reports = obj.device_reports()
    for d in range(ND):
        assert int(res.states.host_pages[d]) == reports[d]["host_pages"]
        assert int(res.states.dummy_pages[d]) == reports[d]["dummy_pages"]
    if where == "after" and policy == "traditional":
        # no row follows the rebuild: one order, every key equal
        for got_rep, want_rep in zip(arr.device_reports(), reports):
            assert got_rep == want_rep


def _rows_of_zone(rows, z):
    return [i for i, r in enumerate(rows) if r[1] == z]


def test_merge_holds_a_reset_behind_its_zones_rebuild():
    """A zone reset right after the failure: plain round robin would
    read or rebuild it after its RESET; the merge holds the RESET (and,
    on the replacement, every row of the zone) until its rebuild is
    out."""
    reb = [(E.OP_READ, 0, 8, 0, 3), (E.OP_READ, 0, 8, 0, 3),
           (E.OP_READ, 1, 8, 0, 3), (E.OP_READ, 1, 8, 0, 3)]
    fg = [(E.OP_WRITE, 2, 4, 1, 0), (E.OP_RESET, 1, 0, 0, 1),
          (E.OP_WRITE, 1, 4, 1, 1), (E.OP_RESET, 0, 0, 0, 0)]
    plain = []
    for i in range(4):
        plain += [fg[i], reb[i]]
    assert plain.index(fg[1]) < plain.index(reb[3])    # broken order
    out, held = merge_rebuild(fg, reb, replacement=False)
    assert sorted(out) == sorted(fg + reb) and held == 1
    assert out.index(fg[1]) > max(out.index(r) for r in reb[2:])
    assert [r for r in out if r[4] == 3] == reb
    assert [r for r in out if r[4] != 3] == fg
    rep = [(E.OP_WRITE, 1, 8, 1, 3), (E.OP_WRITE, 0, 8, 1, 3)]
    out, held = merge_rebuild([(E.OP_WRITE, 0, 4, 1, 0)], rep,
                              replacement=True)
    assert out == rep + [(E.OP_WRITE, 0, 4, 1, 0)] and held == 1


def test_fleet_rebuild_orders_every_zone_on_a_churning_stream(eng, mix):
    """On a stream that resets superzones live at the failure while
    their rebuild is queued, every lane's rebuild rows of a zone come
    before that lane's next RESET of it, and on the replacement before
    any other row of it; the engine accepts every row."""
    fc = _config(SUPERBLOCK, "traditional", 0.5)
    programs, dyn, merged, rebuilds = fleet_batch(eng, [fc],
                                                  n_devices=ND)
    prof = Profiler()
    with prof.section("outer"):
        failed = stripe_rebuild(
            merged[0], n_devices=ND, chunk_pages=CHUNK, parity=True,
            member_zone_pages=64, parity_tenant=N_TENANTS, member=ND - 1,
            at_row=len(merged[0]) // 2)
    assert prof.counters["build.rebuild_held_rows"] > 0
    for d, lane in enumerate(failed.lanes):
        rows = [tuple(r) for r in lane.tolist()][failed.marks[d]:]
        np.testing.assert_array_equal(
            lane, programs[d][programs[d, :, 0] != E.OP_NOP])
        for z in {r[1] for r in rows if r[4] == REBUILD_TAG}:
            last_rb = max(i for i in _rows_of_zone(rows, z)
                          if rows[i][4] == REBUILD_TAG)
            later = [i for i in _rows_of_zone(rows, z)
                     if rows[i][4] != REBUILD_TAG
                     and (d == ND - 1 or rows[i][0] == E.OP_RESET)]
            assert all(i > last_rb for i in later), (d, z)
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                    rebuilds=rebuilds)
    assert res.ok[programs[:, :, 0] != E.OP_NOP].all()


@pytest.mark.parametrize("policy", ["traditional", "silent"])
@pytest.mark.parametrize("spec", [SUPERBLOCK, vchunk(2)],
                         ids=lambda s: s.name)
def test_rebuild_clock_starts_at_the_failure_and_waits_for_the_reads(
        eng, mix, spec, policy):
    """Lanes keep separate clocks, yet no row issued after the failure
    starts before the failure instant (the latest completion of a row
    issued before it, over the array), every chunk appended to the
    replacement starts after each survivor read it is computed from,
    and so the time to recover holds the replacement's writes."""
    fc = _config(spec, policy, 0.6)
    programs, dyn, _, rebuilds = fleet_batch(eng, [fc], n_devices=ND)
    res = run_fleet(eng, programs, dyn=dyn, n_tenants=N_TENANTS,
                    rebuilds=rebuilds)
    P = res.cols.shape[-1]
    t_page = np.where(programs[:, :, 0] == E.OP_READ,
                      np.float32(eng.flash.t_read + eng.flash.t_xfer),
                      np.float32(eng.flash.t_prog + eng.flash.t_xfer))
    dur = ((res.pages + P - 1) // P).astype(np.float32) * t_page
    act = res.pages > 0
    after = (np.arange(programs.shape[1])[None, :]
             >= rebuilds.marks[:, None])
    instant = res.completions[~after].max()
    assert instant > 0 and (after & act).any()
    # done = start + dur, rounded once: a start at or after t gives a
    # completion at or after the rounded t + dur
    assert (res.completions[after & act]
            >= (instant + dur)[after & act]).all()
    rep = ND - 1
    rb = (programs[:, :, 4] == REBUILD_TAG) & act
    writes = np.flatnonzero(rb[rep] & (programs[rep, :, 0] == E.OP_WRITE))
    lane, row, src_lane, src_row = rebuilds.waits.T
    assert set(row) == set(writes) and (lane == rep).all()
    assert (src_lane != rep).all()
    assert (programs[src_lane, src_row, 0] == E.OP_READ).all()
    assert (programs[src_lane, src_row, 1] == programs[lane, row, 1]).all()
    assert (res.completions[lane, row] >= (
        res.completions[src_lane, src_row] + dur[lane, row])).all()
    (report,) = Evaluator(eng, n_devices=ND).evaluate([fc])
    assert report["recover_s"] == float(
        res.completions[rb].max() - instant)
    # the replacement's rebuild is one closed-loop stream after the
    # failure: its writes alone take their summed service time
    assert report["recover_s"] >= 0.999 * float(dur[rep][rb[rep]].sum())


def test_plan_rebuild_is_the_array_engines(eng):
    """``ArrayEngine.rebuild_device`` emits :func:`plan_rebuild`'s
    steps: its read plan and the replacement's rows."""
    arr = ArrayEngine.build(flash(), ZoneGeometry(4, 4), SUPERBLOCK,
                            n_devices=ND, chunk_pages=CHUNK, parity=True,
                            max_active=6)
    for z, n in ((0, 40), (1, 96), (2, 17)):
        arr.zone_write(z, n)
    arr.zone_finish(2)
    steps = plan_rebuild(arr.zones, 1, chunk_pages=CHUNK, n_devices=ND,
                         stripes_per_zone=arr.stripes_per_zone)
    arr.fail_device(1)
    plan = arr.rebuild_device(1)
    assert plan == [(m, z, off, n) for m, op, z, off, n in steps if m != 1]
    assert [r[:3] for r in arr._rows[1]] == [
        (op, z, n) for m, op, z, _, n in steps if m == 1]
    assert any(op == E.OP_FINISH for _, op, *_ in steps)


DIGESTS = {
    "programs":
        "21630f75938905b309856d51d1c3a4f46743311970d05aac6ab0d6835e58e682",
    "rows":
        "2ab8200df9a81ce76d04ba82284d23a106d918a5ba74b139dcf5900c6989cd8a",
    "random":
        "b2b23bd0f6e607b1cf9eb3a7c2304f5f0b5fb3ef19b0a8da9945228c6a74c680",
}


@pytest.mark.parametrize("what", sorted(DIGESTS))
def test_healthy_configs_build_what_they_built_before_failures(what):
    """Without a failure the builder, the rows and the sampled configs
    are byte for byte what they were before the failure axis existed
    (digests taken then, on this grid)."""
    f = FlashGeometry(n_channels=4, ways_per_channel=1, blocks_per_lun=16,
                      pages_per_block=4, page_bytes=4096)
    e = E.ZoneEngine(f, ZoneGeometry(4, 4), SPECS, max_active=6)
    axes = dict(segments=(4, 2), chunks=(8, 16), parities=(False, True),
                wear=(True, False), specs=SPECS,
                policies=("traditional", "silent"))
    configs = grid_space(**axes)
    if what == "programs":
        programs, _, merged = build_fleet_batch(e, configs, n_devices=3,
                                                pad_quantum=16)
        h = hashlib.sha256(programs.tobytes())
        for m in merged:
            h.update(m.tobytes())
    elif what == "rows":
        rows = Evaluator(e, n_devices=3).evaluate(configs[::7])
        h = hashlib.sha256(json.dumps(rows, sort_keys=True).encode())
    else:
        h = hashlib.sha256(json.dumps([
            fc.describe() for fc in random_space(12345, 9, **axes)
        ]).encode())
    assert h.hexdigest() == DIGESTS[what]


def test_failures_axis_joins_the_codec_only_when_searched():
    base = SearchSpace(mixes=("dlwa_pair",), specs=SPECS)
    assert base.axes == SearchSpace(mixes=("dlwa_pair",), specs=SPECS,
                                    failures=(None,)).axes
    space = SearchSpace(mixes=("dlwa_pair",), specs=SPECS,
                        failures=(None, (3, 0.75)))
    assert len(space) == 2 * len(base)
    for fc in space.grid():
        assert space.decode(space.encode(fc)) == fc
    failed = [fc for fc in space.grid() if fc.failure is not None]
    assert failed and all(fc.describe().endswith("_fail3at0.75")
                          for fc in failed)
    with pytest.raises(ValueError, match="no failures axis"):
        base.encode(failed[0])


@pytest.mark.parametrize("where", ["striper", "builder"])
def test_a_rebuild_without_parity_is_refused(eng, mix, where):
    if where == "striper":
        with pytest.raises(ValueError, match="needs parity"):
            stripe_rebuild(_mix(eng, 96)[0], n_devices=ND, chunk_pages=8,
                           parity=False, member_zone_pages=64,
                           parity_tenant=N_TENANTS, member=3, at_row=5)
    else:
        with pytest.raises(ValueError, match="needs parity"):
            build_fleet_batch(eng, [_config(SUPERBLOCK, "traditional",
                                            0.5, parity=False)],
                              n_devices=ND)


@pytest.mark.parametrize("failed", [False, True])
def test_rebuild_tag_joins_the_timing_only_with_a_failure(eng, mix,
                                                         monkeypatch,
                                                         failed):
    seen = []
    simulate = timing.simulate_fleet_ops

    def spy(*args):
        seen.append(args[5])                # n_tenants
        return simulate(*args)

    monkeypatch.setattr(timing, "simulate_fleet_ops", spy)
    fc = _config(SUPERBLOCK, "traditional", 0.5)
    if not failed:
        fc = FleetConfig(MIX, 4, CHUNK, True, True, SUPERBLOCK)
    (row,) = Evaluator(eng, n_devices=ND).evaluate([fc])
    # a rebuild runs the clock three times (failure instant, survivors,
    # replacement), each with the rebuild's stream
    assert seen == ([REBUILD_TAG + 1] * 3 if failed else [N_TENANTS + 1])
    new = {"failed_member", "fail_at", "recover_s", "rebuild_pages"} | {
        f"tenant{k}_p99_after_failure_s" for k in range(N_TENANTS)} | {
        f"member{d}_dlwa" for d in range(ND)}
    assert new & set(row) == (new if failed else set())


def test_recovery_rollups_read_the_rebuild(eng, mix):
    fc = _config(SUPERBLOCK, "silent", 0.5)
    prof = Profiler()
    (row,) = Evaluator(eng, n_devices=ND, profiler=prof).evaluate([fc])
    assert row["rebuild_pages"] > 0 and row["recover_s"] > 0
    assert all(row[f"member{d}_dlwa"] >= 1.0 for d in range(ND))
    assert {"build.rebuild", "fleet.recover"} <= set(prof.sections)
    programs = build_fleet_batch(eng, [fc], n_devices=ND)[0]
    ops, tags = programs[:, :, 0], programs[:, :, 4]
    rb = tags == REBUILD_TAG
    assert prof.counters["build.rebuild_rows"] == rb.sum()
    assert prof.counters["build.rebuild_read_pages"] == programs[
        rb & (ops == E.OP_READ)][:, 2].sum()
    assert row["rebuild_pages"] == programs[
        rb & (ops == E.OP_WRITE)][:, 2].sum()


def test_counters_are_listed_and_need_a_profiler(eng, mix):
    count("build.rebuild_rows", 5)            # no profiler: nothing
    prof = Profiler()
    with prof.section("outer"):
        count("build.rebuild_rows", 5)
        count("build.rebuild_rows")
        count("any.name", 2)
    assert prof.counters == {"build.rebuild_rows": 6.0, "any.name": 2.0}
    # a failed config's build lists the rebuild's three counters, its
    # dispatch the engine's three and its rollups their one state read
    prof = Profiler()
    Evaluator(eng, n_devices=ND, profiler=prof).evaluate(
        [_config(SUPERBLOCK, "traditional", 0.5)])
    assert set(prof.counters) == set(COUNTERS) | {
        "engine.groups", "engine.lane_steps", "engine.alloc_steps",
        "rollup.device_reads"}


def test_obs_report_shows_the_rebuild_spans_and_counters(eng, mix):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    prof = Profiler()
    Evaluator(eng, n_devices=ND, profiler=prof).evaluate(
        [_config(BLOCK, "traditional", 0.6)])
    report = obs_report.render(
        {"timelines": {}, "profile": prof.snapshot(),
         "metrics": {"counters": dict(prof.counters)}}, max_lanes=1)
    for name in ("build.rebuild", "fleet.recover", *COUNTERS):
        assert name in report


def test_stripe_program_is_the_rebuild_stripers_healthy_half(eng):
    """With the failure after the last row and nothing live, the
    rebuild striper's lanes are the plain striper's, less the failed
    member's."""
    prog = E.encode_program([(E.OP_WRITE, 0, 20, E.F_HOST, 0),
                             (E.OP_RESET, 0, 0, 0, 1)], width=5)
    kw = dict(n_devices=ND, chunk_pages=8, parity=True,
              member_zone_pages=64, parity_tenant=N_TENANTS)
    plain = stripe_program(prog, **kw)
    failed = stripe_rebuild(prog, member=1, at_row=2, **kw)
    assert failed.waits.shape == (0, 3) and failed.marks[1] == 0
    for d in range(ND):
        want = plain[d] if d != 1 else plain[d][:0]
        np.testing.assert_array_equal(failed.lanes[d], want)
