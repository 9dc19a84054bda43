"""Driver: the fleet design-space search with one member failing and
rebuilt under the tenants' load.

Every call is one ``repro.fleet.Evaluator.evaluate`` over the traffic's
grid, as the ``fleet`` driver makes it, with each config's array
losing the member the configuration's ``failure`` names at its share
of the merged tenant stream (the search's ``failures`` axis).  The
comparison with the plain reference is the ``fleet`` driver's, with
the failure, the rebuild plan, the merge and the recovery rollups of
``reference/rebuild.py`` on top of the striped lanes.

Configuration keys read: those of the ``fleet`` driver, and
``failure`` (``member``, ``at``).  Traffic keys read: those of the
``fleet`` driver.
"""

from __future__ import annotations

import importlib.util
import pathlib
from typing import Dict, List

import numpy as np

from repro.core.engine import OP_NOP
from repro.fleet import search

import reference.check as ref_check
from reference import rebuild as ref_rebuild
from reference import stripe as ref_stripe

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_fleet_base", pathlib.Path(__file__).with_name("fleet.py"))
fleet = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fleet)


class Driver(fleet.Driver):
    def setup(self) -> None:
        f = self.config["failure"]
        self.failure = (int(f["member"]), float(f["at"]))
        self._ref_failed: Dict = {}
        super().setup()
        self.space["failures"] = (self.failure,)
        self.grid = search.grid_space(**self.space)

    def failed(self, fc) -> ref_rebuild.Failed:
        """The reference's lanes and failure marks of one config."""
        cap = self.capacities([fc])[0]
        key = (cap, fc.chunk_pages)
        if key not in self._ref_failed:
            striped = super().reference_lanes([fc])
            seg = self.config["zone"]["parallelism"] * \
                self.config["flash"]["pages_per_block"]
            self._ref_failed[key] = ref_rebuild.fail(
                self._ref_mixes[cap], striped, member=fc.failure[0],
                at=fc.failure[1], n_devices=self.n_devices,
                chunk_pages=fc.chunk_pages,
                member_zone_pages=seg * fc.n_segments,
                parity_tenant=search.N_TENANTS)
        return self._ref_failed[key]

    def reference_lanes(self, configs) -> List[np.ndarray]:
        lanes: List[np.ndarray] = []
        for fc in configs:
            lanes += self.failed(fc).lanes
        return lanes

    def check(self, substitute=None) -> Dict[str, Dict]:
        """The ``fleet`` driver's comparison over the failed arrays,
        with the rebuild's tag and the failures' order on the reference
        clock and the recovery rollups in each row.  The control's clock
        counts tags up to the ``parity_tenant`` it is handed, so it is
        handed the rebuild's; it keeps no failure order."""
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
        nd = self.n_devices
        ref = ref_rebuild.run_reference(
            static, flash, programs, values, search.N_TENANTS,
            [(k * nd, self.failed(fc)) for k, fc in enumerate(configs)])
        lanes = np.arange(len(programs))
        ref_rows = {}
        for k, fc in enumerate(configs):
            idx = np.arange(k * nd, (k + 1) * nd)
            row = ref_check.config_row(ref, idx, search.N_TENANTS)
            row.update(ref_rebuild.recovery_row(
                ref, idx, self.failed(fc).marks, search.N_TENANTS))
            ref_rows[fc.describe()] = row
        call_rows = list(self.rows)
        if substitute is not None:
            got, states, call_rows = substitute(
                self, got, states, call_rows,
                dict(ref, parity_tenant=search.N_TENANTS + 1))
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
