"""Driver: a closed-loop caller of the fleet design-space search.

Every call is one ``repro.fleet.Evaluator.evaluate`` over a set of
``FleetConfig``s, as ``benchmarks/fleet_search.py`` runs it: the whole
grid in an order drawn from the run's seed and the call's index, or
``configs_per_call`` configs drawn from the grid with that seed.  The
tenants' traffic is the KVBench LSM run that the traffic file states,
recorded once per array capacity in set-up and handed out again on
every call, as the library caches its recorded mixes.  It is
registered under a mix name of the benchmark's own, so a later change
to a library default cannot move it.

Configuration keys read: the device (``flash``, ``zone``,
``max_active``, ``specs``), ``array.members``, ``tenants`` (count,
window and active zones per tenant), ``kvbench`` and
``zenfs.finish_threshold``.  Traffic keys read: ``grid`` (the axes),
``configs_per_call`` ("grid" or a count), ``tenant_seeds`` (one KVBench
seed per tenant) and, where given, ``pad_quantum`` (else the
``Evaluator``'s own).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import repro.storage  # noqa: F401  (the recorder and the LSM front-end)
from repro.core.elements import BLOCK, SUPERBLOCK, hchunk, vchunk
from repro.core.engine import OP_NOP, ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.fleet import runner, search
from repro.storage.compile import RecordingBackend, record_lsm
from repro.storage.lsm import KVBenchConfig

import reference.check as ref_check
from reference import elements as ref_elements
from reference import geometry as ref_geometry
from reference import recorder as ref_recorder
from reference import stripe as ref_stripe
from reference.static import lane_values, union_static

MIX = "bench_kvbench_lsm"


def spec_of(name: str, elements=None):
    """An element spec from its name (``block``, ``superblock``,
    ``vchunk<s>``, ``hchunk<s>``)."""
    if elements is None:
        table = {"block": BLOCK, "superblock": SUPERBLOCK}
        make = {"vchunk": vchunk, "hchunk": hchunk}
    else:
        table = {"block": elements.BLOCK, "superblock": elements.SUPERBLOCK}
        make = {"vchunk": elements.vchunk, "hchunk": elements.hchunk}
    if name in table:
        return table[name]
    return make[name[:6]](int(name[6:]))


def call_seed(seed: int, index: int) -> int:
    """A 32-bit seed made of the run's seed and a call index."""
    return int(np.random.SeedSequence(
        [seed & (2**63 - 1), index + 2]).generate_state(1)[0])


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rng = np.random.default_rng(call_seed(seed, -2))
        self.kept = None          # (index, configs, FleetResult) of one call
        self.rows: List = []      # (configs, rows) of every call
        self.real_ops: List = []  # (configs, real ops) of every call
        self._ref_mixes: Dict = {}
        self._ref_lanes: Dict = {}

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        c, t = self.config, self.traffic
        self.flash = FlashGeometry(**c["flash"])
        self.zone = ZoneGeometry(**c["zone"])
        self.specs = [spec_of(s) for s in c["specs"]]
        self.eng = ZoneEngine(self.flash, self.zone, self.specs,
                              max_active=c["max_active"])
        g = t["grid"]
        self.space = dict(
            mixes=(MIX,), segments=tuple(g["segments"]),
            chunks=tuple(g["chunks"]), parities=tuple(g["parities"]),
            wear=tuple(g["wear"]),
            specs=tuple(spec_of(s) for s in g["specs"]),
            policies=tuple(g["policies"]))
        self.grid = search.grid_space(**self.space)
        self.n_devices = c["array"]["members"]
        if len(t["tenant_seeds"]) != c["tenants"]["count"]:
            raise ValueError("one KVBench seed per tenant")
        self._mixes: Dict[int, List[np.ndarray]] = {}
        search.MIXES[MIX] = self._mix
        for cap in self.capacities(self.grid):
            self._mix(self.eng, cap)
        kw = ({"pad_quantum": t["pad_quantum"]} if "pad_quantum" in t
              else {})
        self.ev = search.Evaluator(self.eng, n_devices=self.n_devices,
                                   check_legal=True, **kw)
        # the result of each call's one dispatch, as the Evaluator
        # receives it (the rows alone do not carry the lanes' rows)
        run_fleet = getattr(runner.run_fleet, "__wrapped__",
                            runner.run_fleet)

        def capture(*args, **kw):
            self._last = run_fleet(*args, **kw)
            return self._last

        capture.__wrapped__ = run_fleet
        runner.run_fleet = capture

    def capacities(self, configs) -> List[int]:
        """The logical superzone capacity (pages) of each config."""
        seg = self.config["zone"]["parallelism"] * \
            self.config["flash"]["pages_per_block"]
        return [(self.n_devices - (1 if fc.parity else 0)) * seg
                * fc.n_segments for fc in configs]

    def _mix(self, eng, cap: int) -> List[np.ndarray]:
        """The tenants' recorded programs at one array capacity."""
        if cap not in self._mixes:
            c = self.config
            win = c["tenants"]["window_zones"]
            progs = []
            for k, seed in enumerate(self.traffic["tenant_seeds"]):
                dev = RecordingBackend(
                    _mix_flash(self.flash.page_bytes), zone_pages=cap,
                    n_zones=win, max_active=c["tenants"]["active_zones"],
                    zone_base=k * win)
                record_lsm(dev, cfg=KVBenchConfig(**c["kvbench"], seed=seed),
                           finish_threshold=c["zenfs"]["finish_threshold"])
                progs.append(dev.program())
            self._mixes[cap] = progs
        return [p.copy() for p in self._mixes[cap]]

    def reference_lanes(self, configs) -> List[np.ndarray]:
        """Every lane's op rows for a config list, rebuilt by the plain
        reference from the configuration and the tenant seeds: the
        tenants recorded, merged and striped over the members."""
        c = self.config
        win = c["tenants"]["window_zones"]
        seg = c["zone"]["parallelism"] * c["flash"]["pages_per_block"]
        lanes: List[np.ndarray] = []
        for fc, cap in zip(configs, self.capacities(configs)):
            if cap not in self._ref_mixes:
                self._ref_mixes[cap] = ref_stripe.interleave([
                    ref_stripe.tag(ref_recorder.record_kvbench(
                        c["kvbench"], seed,
                        page_bytes=c["flash"]["page_bytes"], zone_pages=cap,
                        n_zones=win, max_active=c["tenants"]["active_zones"],
                        finish_threshold=c["zenfs"]["finish_threshold"],
                        zone_base=k * win), k)
                    for k, seed in enumerate(self.traffic["tenant_seeds"])])
            key = (cap, fc.chunk_pages, fc.parity)
            if key not in self._ref_lanes:
                self._ref_lanes[key] = ref_stripe.stripe(
                    self._ref_mixes[cap], n_devices=self.n_devices,
                    chunk_pages=fc.chunk_pages, parity=fc.parity,
                    member_zone_pages=seg * fc.n_segments,
                    parity_tenant=search.N_TENANTS)
            lanes += self._ref_lanes[key]
        return lanes

    def _configs(self, index: int):
        n = self.traffic["configs_per_call"]
        seed = call_seed(self.seed, index)
        if n == "grid":
            order = np.random.default_rng(seed).permutation(len(self.grid))
            return [self.grid[k] for k in order]
        return search.random_space(seed, n, **self.space)

    # -- the timed call --------------------------------------------------
    def call(self, index: int, profiler) -> Dict:
        configs = self._configs(index)
        self.ev.profiler = profiler
        if profiler is None:
            rows = self.ev.evaluate(configs)
        else:
            with profiler.section("call"):
                rows = self.ev.evaluate(configs)
        res = self._last
        self._last = None
        ops = res.programs[:, :, 0]
        real = int((ops != OP_NOP).sum())
        if index >= 0:
            self.rows.append((configs, rows))
            self.real_ops.append((configs, real))
            # keep one call's whole result, drawn uniformly from the seed
            if self.rng.random() < 1.0 / len(self.rows):
                self.kept = (index, configs, res)
        return {"real_ops": real, "cells": int(ops.size)}

    # -- the comparison with the plain reference -------------------------
    def reference_inputs(self, configs):
        """Per-lane reference specs and values of a config list."""
        c = self.config
        flash = ref_geometry.FlashGeometry(**c["flash"])
        zone = ref_geometry.ZoneGeometry(**c["zone"])
        static = union_static(
            flash, zone, [spec_of(s, ref_elements) for s in c["specs"]],
            c["max_active"])
        seg_pages = zone.parallelism * flash.pages_per_block
        specs, values = [], []
        for fc in configs:
            mix = [spec_of(s.name, ref_elements) for s in fc.specs_mix()]
            for d in range(self.n_devices):
                specs.append(mix[d % len(mix)])
                values.append(lane_values(
                    static, specs[-1], zone_pages=seg_pages * fc.n_segments,
                    wear_aware=fc.wear_aware, alloc_policy=fc.alloc_policy))
        return flash, zone, static, specs, values

    def check(self, substitute=None) -> Dict[str, Dict]:
        """Compare the kept call's lanes, and every call's rows, with the
        plain reference: the op rows rebuilt from the configuration, the
        device model and the clock on every lane, the legacy device on
        every lane, and every call's count of real ops.  ``substitute``
        (the control) replaces the program's arrays and rows before the
        comparison."""
        _, configs, res = self.kept
        got = {k: np.asarray(getattr(res, k)) for k in (
            "ok", "host_delta", "dummy_delta", "erase_delta",
            "completions", "latencies", "makespans")}
        states = {f: np.asarray(getattr(res.states, f))
                  for f in ref_check.STATE_FIELDS}
        programs = np.asarray(res.programs)
        want = self.reference_lanes(configs)
        op_rows = ref_check.count_op_rows(programs, want)
        real_ops = sum(abs(n - sum(len(p) for p in self.reference_lanes(fcs)))
                       for fcs, n in self.real_ops)
        if op_rows == 0:     # the reference prices its own rows
            programs = ref_stripe.pad(want, programs.shape[1])
        flash, zone, static, specs, values = self.reference_inputs(configs)
        ref = ref_check.run_reference(static, flash, programs, values,
                                      search.N_TENANTS)
        lanes = np.arange(len(programs))
        nd = self.n_devices
        ref_rows = {fc.describe(): ref_check.config_row(
            ref, np.arange(k * nd, (k + 1) * nd), search.N_TENANTS)
            for k, fc in enumerate(configs)}
        call_rows = list(self.rows)
        if substitute is not None:
            got, states, call_rows = substitute(self, got, states,
                                                call_rows, ref)
        got_rows, want_rows = [], []
        for fcs, rows in call_rows:
            for fc, row in zip(fcs, rows):
                if fc.describe() in ref_rows:
                    got_rows.append(row)
                    want_rows.append(ref_rows[fc.describe()])
        illegal = int(((programs[:, :, 0] != OP_NOP) & ~got["ok"]).sum())
        counts = {"op_rows": op_rows, "real_ops": real_ops,
                  "illegal_ops": illegal,
                  "ok_and_deltas": ref_check.count_deltas(got, ref, lanes),
                  "final_state": ref_check.count_states(
                      states, ref, lanes, static.n_elements),
                  "clock_bits": ref_check.count_clock(got, ref, lanes),
                  "rows": ref_check.count_rows(got_rows, want_rows),
                  "legacy": ref_check.count_legacy(
                      flash, zone, specs, values, programs, states,
                      list(lanes), static.max_active, static.per_group)}
        counts["no_rows_compared"] = int(not want_rows)
        return {k: {"value": v, "limit": 0} for k, v in counts.items()}


def _mix_flash(page_bytes: int) -> FlashGeometry:
    """A geometry carrying only what the front-end reads off a recorder
    (``page_bytes``); the engine supplies the real geometry."""
    return FlashGeometry(n_channels=1, ways_per_channel=1, blocks_per_lun=1,
                         pages_per_block=1, page_bytes=page_bytes)
