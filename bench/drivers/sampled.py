"""Driver: the fleet search's random strategy, small sampled batches
back to back.

Every call is the ``fleet`` driver's with ``configs_per_call`` configs
drawn from the grid by ``random_space`` (from the run's seed and the
call's index), padded by the ``Evaluator``'s own pad quantum, as
``fleet_search --strategy random`` runs them.  Batches of different
configs then pad to different scan lengths, and each length compiles
once.  Set-up compiles every length a draw can reach: a batch is as
long as its longest config, rounded up to the quantum, so a length is
reached where some config has it and ``configs_per_call`` configs have
it or less.

A row's ``ops_ok`` counts the NOP rows that pad its lanes, so the same
config reads differently in batches of different lengths.  The
comparison is the ``fleet`` driver's over the calls that ran at the
kept call's length; every other call's rows are compared with the
reference rows of the kept call's configs replayed at that call's
length.

Configuration and traffic keys read: those of the ``fleet`` driver.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Dict

import numpy as np

from repro.fleet import search

import reference.check as ref_check
from reference import stripe as ref_stripe

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_fleet_base", pathlib.Path(__file__).with_name("fleet.py"))
fleet = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fleet)


class Driver(fleet.Driver):
    def setup(self) -> None:
        super().setup()
        self.lengths = []         # each call's scan length
        n, q = self.traffic["configs_per_call"], self.ev.pad_quantum
        # a config's lanes depend on its array capacity, chunk and
        # parity, not on its spec, allocator or policy
        built = {}
        lengths = []
        for fc in self.grid:
            key = (fc.n_segments, fc.chunk_pages, fc.parity)
            if key not in built:
                programs = search.build_fleet_batch(
                    self.eng, [fc], n_devices=self.n_devices)[0]
                built[key] = -(-programs.shape[1] // q) * q
            lengths.append(built[key])
        #: the scan lengths warmed in set-up
        self.warm_lengths = []
        for length in sorted(set(lengths)):
            at = [fc for fc, k in zip(self.grid, lengths) if k == length]
            below = [fc for fc, k in zip(self.grid, lengths) if k < length]
            if len(at) + len(below) >= n:
                self.ev.evaluate(at[:1] + (below + at[1:])[:n - 1])
                self.warm_lengths.append(length)
        self._last = None
        print(json.dumps({"warm_lengths": self.warm_lengths}),
              file=sys.stderr)

    def call(self, index: int, profiler) -> Dict:
        out = super().call(index, profiler)
        if index >= 0:
            lanes = self.traffic["configs_per_call"] * self.n_devices
            self.lengths.append(out["cells"] // lanes)
        return out

    def check(self, substitute=None) -> Dict[str, Dict]:
        """The ``fleet`` driver's comparison over the calls at the kept
        call's length, and under no substitute the other calls' rows
        against the reference at their own lengths."""
        n_kept = self.kept[2].programs.shape[1]
        rows = self.rows
        self.rows = [r for r, n in zip(rows, self.lengths) if n == n_kept]
        try:
            counts = super().check(substitute)
        finally:
            self.rows = rows
        if substitute is None:
            counts["rows"]["value"] += self.other_lengths(n_kept)
        return counts

    def other_lengths(self, n_kept: int) -> int:
        """Row values of calls at another length than the kept call's
        that differ from the reference rows of the kept call's configs
        replayed at that length."""
        _, configs, _ = self.kept
        flash, _, static, _, values = self.reference_inputs(configs)
        nd, bad = self.n_devices, 0
        for n in sorted(set(self.lengths) - {n_kept}):
            calls = [r for r, k in zip(self.rows, self.lengths) if k == n]
            seen = {fc.describe() for fcs, _ in calls for fc in fcs}
            pick = [k for k, fc in enumerate(configs)
                    if fc.describe() in seen]
            if not pick:
                continue
            want = self.reference_lanes([configs[k] for k in pick])
            programs = ref_stripe.pad(
                want, max([n] + [len(p) for p in want]))
            ref = ref_check.run_reference(
                static, flash, programs,
                [values[k * nd + d] for k in pick for d in range(nd)],
                search.N_TENANTS)
            ref_rows = {configs[k].describe(): ref_check.config_row(
                ref, np.arange(i * nd, (i + 1) * nd), search.N_TENANTS)
                for i, k in enumerate(pick)}
            got_rows, want_rows = [], []
            for fcs, call_rows in calls:
                for fc, row in zip(fcs, call_rows):
                    if fc.describe() in ref_rows:
                        got_rows.append(row)
                        want_rows.append(ref_rows[fc.describe()])
            bad += ref_check.count_rows(got_rows, want_rows)
        return bad
