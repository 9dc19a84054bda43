"""The control of ``correct``: the plain reference put in the program's
place with its clock computed one precision lower (bfloat16 for the
float32 the configurations state), which the comparison has to refuse.

For each seed this sets a cell up, runs its calls for ``--seconds`` at
the cell's own size and load, and prints one JSON line with every
number the comparison makes for the program (the lower readings) and
for the control (the upper readings).  The benchmark's own runs never
run it::

    python3 bench/control.py --workload zenfs.kvbench --seeds 1,2,3 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import harness  # noqa: E402


def lower_precision(driver, got, states, answers, ref):
    """The reference's own answers with the busy clock in bfloat16."""
    import ml_dtypes

    from reference import clock
    from reference.check import (DELTAS, STATE_FIELDS, class_report,
                                 config_row, lane_row)

    low = dict(ref)
    done, lat, span = clock.busy_clock(
        ref["cols"], ref["pages"], ref["programs"][:, :, 4], ref["t_page"],
        ref["n_luns"], ref["parity_tenant"] + 1, dtype=ml_dtypes.bfloat16)
    low.update(completions=done.astype(np.float32),
               latencies=lat.astype(np.float32),
               makespans=span.astype(np.float32))
    got = {k: low[k] for k in DELTAS + ("completions", "latencies",
                                        "makespans")}
    states = {f: np.stack([np.asarray(s[f]) for s in low["states"]])
              for f in STATE_FIELDS}
    if isinstance(answers, dict):                  # a replay's report
        answers = {"classes": class_report(low, driver.classes),
                   "lanes": [lane_row(low, j)
                             for j in range(len(low["states"]))]}
    else:                                          # fleet rows per call
        nd = driver.n_devices
        rows = {fc.describe(): config_row(low, np.arange(k * nd,
                                                         (k + 1) * nd), 2)
                for k, fc in enumerate(driver.kept[1])}
        answers = [(fcs, [rows.get(fc.describe(), {}) for fc in fcs])
                   for fcs, _ in answers]
    return got, states, answers


def main(argv=None, *, require_chip: bool = True,
         root: pathlib.Path = harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic = harness.load_cell(args.workload, root)
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    mod = harness.load_module("drivers", traffic["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        driver = mod.Driver(config, traffic, seed)
        driver.setup()
        driver.call(-1, None)
        window = harness.run_calls(driver, 0, args.seconds, None)
        program = driver.check()
        control = driver.check(substitute=lower_precision)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "calls": len(window["calls"]), "failed": window["failed"],
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
