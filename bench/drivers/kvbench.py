"""Driver: RocksDB on ZenFS under KVBench, recorded and replayed per call.

Every call records one fresh KVBench run through ``RecordingBackend``
and ``repro.storage.compile.record_lsm`` (its seed made of the run's
seed and the call's index), replays it with ``replay_recorders`` on the
lanes the traffic file lists (one element spec and allocation policy
each), and reads what a user reads: the per-class report and each
lane's DLWA, erases and wear.

Configuration keys read: the device (``flash``, ``zone``,
``max_active``, ``specs``), ``kvbench`` (the KVBench config without its
seed) and ``zenfs`` (the zones it mounts, its finish threshold).
Traffic keys read: ``classes``, ``lanes``, ``pad_ops``,
``checked_calls``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List

import numpy as np

from repro.core.engine import OP_NOP, ZoneEngine
from repro.core.geometry import FlashGeometry, ZoneGeometry
from repro.storage.compile import (RecordingBackend, lane_metrics,
                                   record_lsm, replay_recorders)
from repro.storage.lsm import KVBenchConfig

import reference.check as ref_check
from drivers.fleet import call_seed, spec_of
from reference import elements as ref_elements
from reference import geometry as ref_geometry
from reference import recorder as ref_recorder
from reference import stripe as ref_stripe
from reference.static import lane_values, union_static


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rng = np.random.default_rng(call_seed(seed, -2))
        self.kept: List = []     # seeded reservoir of calls to compare
        self.longest = None      # the call with the most real ops
        self.n_calls = 0

    def setup(self) -> None:
        c, t = self.config, self.traffic
        self.flash = FlashGeometry(**c["flash"])
        self.zone = ZoneGeometry(**c["zone"])
        self.eng = ZoneEngine(self.flash, self.zone,
                              [spec_of(s) for s in c["specs"]],
                              max_active=c["max_active"])
        self.classes = tuple(t["classes"])
        self.dyns = [self.eng.dyn(spec=spec_of(lane["spec"]),
                                  alloc_policy=lane["policy"])
                     for lane in t["lanes"]]

    def call(self, index: int, profiler) -> Dict:
        c, t = self.config, self.traffic
        sec = (profiler.section if profiler is not None
               else lambda _name: contextlib.nullcontext())
        with sec("call"):
            with sec("record"):
                rec = RecordingBackend(
                    self.eng.flash, zone_pages=self.eng.cfg.zone_pages,
                    n_zones=c["zenfs"]["zones"], max_active=c["max_active"],
                    class_tenants={n: k for k, n in enumerate(self.classes)})
                record_lsm(rec, cfg=KVBenchConfig(
                    **c["kvbench"], seed=call_seed(self.seed, index)),
                    finish_threshold=c["zenfs"]["finish_threshold"])
            res = replay_recorders(
                self.eng, [rec] * len(self.dyns), dyns=self.dyns,
                n_tenants=len(self.classes), pad_quantum=t["pad_ops"],
                profiler=profiler)
            answer = {"classes": res.tenant_class_report(
                names=list(self.classes)), "lanes": []}
            for lane in range(len(self.dyns)):
                m = lane_metrics(self.eng, res, lane)
                wear = res.pooled_wear(self.eng, np.asarray([lane]))
                mean_w = float(wear.mean()) if wear.size else 0.0
                answer["lanes"].append({
                    "host_pages": m["host_pages"],
                    "dummy_pages": m["dummy_pages"], "dlwa": m["dlwa"],
                    "block_erases": m["block_erases"],
                    "max_wear": float(wear.max()) if wear.size else 0.0,
                    "wear_cv": (float(wear.std() / mean_w)
                                if mean_w > 0 else 0.0)})
        ops = res.programs[:, :, 0]
        real = int((ops != OP_NOP).sum())
        if index >= 0:
            self._keep((index, res, answer, real))
        return {"real_ops": real, "cells": int(ops.size)}

    def _keep(self, item) -> None:
        """A seeded reservoir of ``checked_calls`` calls, and the call
        with the most real ops."""
        self.n_calls += 1
        k = self.traffic["checked_calls"]
        if len(self.kept) < k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(self.n_calls))
            if j < k:
                self.kept[j] = item
        if self.longest is None or item[3] > self.longest[3]:
            self.longest = item

    def reference_inputs(self):
        c = self.config
        flash = ref_geometry.FlashGeometry(**c["flash"])
        zone = ref_geometry.ZoneGeometry(**c["zone"])
        static = union_static(
            flash, zone, [spec_of(s, ref_elements) for s in c["specs"]],
            c["max_active"])
        specs = [spec_of(lane["spec"], ref_elements)
                 for lane in self.traffic["lanes"]]
        values = [lane_values(static, s, alloc_policy=lane["policy"])
                  for s, lane in zip(specs, self.traffic["lanes"])]
        return flash, zone, static, specs, values

    def reference_rows(self, index: int) -> np.ndarray:
        """The op rows of call ``index``'s recording, rebuilt by the
        plain reference from the configuration and the call's seed."""
        c = self.config
        zp = (c["zone"]["parallelism"] * c["flash"]["pages_per_block"]
              * c["zone"]["n_segments"])
        return ref_recorder.record_kvbench(
            c["kvbench"], call_seed(self.seed, index),
            page_bytes=c["flash"]["page_bytes"], zone_pages=zp,
            n_zones=c["zenfs"]["zones"], max_active=c["max_active"],
            finish_threshold=c["zenfs"]["finish_threshold"],
            class_tenants={n: k for k, n in enumerate(self.classes)})

    def check(self, substitute=None) -> Dict[str, Dict]:
        """Compare every kept call with the plain reference: its op rows
        recorded again from the call's seed, then the device model, the
        clock, the reports and the legacy device over those rows.
        ``substitute`` (the control) replaces the program's arrays and
        answers before the comparison."""
        calls = {item[0]: item for item in self.kept + [self.longest]}
        flash, zone, static, specs, values = self.reference_inputs()
        totals = dict.fromkeys(("op_rows", "illegal_ops", "ok_and_deltas",
                                "final_state", "clock_bits", "rows",
                                "legacy"), 0)
        for index in sorted(calls):
            _, res, answer, _ = calls[index]
            got = {k: np.asarray(getattr(res, k)) for k in (
                "ok", "host_delta", "dummy_delta", "erase_delta",
                "completions", "latencies", "makespans")}
            states = {f: np.asarray(getattr(res.states, f))
                      for f in ref_check.STATE_FIELDS}
            programs = np.asarray(res.programs)
            want = [self.reference_rows(index)] * len(values)
            bad = ref_check.count_op_rows(programs, want)
            totals["op_rows"] += bad
            if bad == 0:     # the reference prices its own rows
                programs = ref_stripe.pad(want, programs.shape[1])
            parity = res.parity_tenant
            ref = ref_check.run_reference(static, flash, programs, values,
                                          parity)
            want = {"classes": ref_check.class_report(ref, self.classes),
                    "lanes": [ref_check.lane_row(ref, j)
                              for j in range(len(values))]}
            if substitute is not None:
                got, states, answer = substitute(self, got, states,
                                                 answer, ref)
            lanes = np.arange(len(programs))
            totals["illegal_ops"] += int(
                ((programs[:, :, 0] != OP_NOP) & ~got["ok"]).sum())
            totals["ok_and_deltas"] += ref_check.count_deltas(got, ref,
                                                              lanes)
            totals["final_state"] += ref_check.count_states(
                states, ref, lanes, static.n_elements)
            totals["clock_bits"] += ref_check.count_clock(got, ref, lanes)
            totals["rows"] += ref_check.count_rows(
                [answer["classes"]] + answer["lanes"],
                [want["classes"]] + want["lanes"])
            totals["legacy"] += ref_check.count_legacy(
                flash, zone, specs, values, programs, states,
                list(lanes), static.max_active, static.per_group)
        return {k: {"value": v, "limit": 0} for k, v in totals.items()}

