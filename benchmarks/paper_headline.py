"""The paper's headline numbers, one batched dispatch per figure.

Reproduces the three summary claims -- ~92% lower DLWA at 10%
occupancy, up to 12% less wear, up to 3.7x faster workload execution --
as SilentZNS-policy vs traditional-mapping lane pairs over ONE shared
union engine (see :mod:`repro.core.headline`):

* DLWA vs occupancy (fill + FINISH at each occupancy point);
* total block erases under RESET churn;
* workload execution time via the op-granular fleet timing model.

Usage::

    PYTHONPATH=src python benchmarks/paper_headline.py \
        [--occupancies 0.1,0.3,0.5] [--zones 4] [--wear-zones 8] \
        [--wear-cycles 8] [--exec-cycles 4] [--wear-bound N] \
        [--quick] [--out paper_headline.json]

With ``--out BENCH_paper.json`` it writes the committed artifact.  The
report is then held to the gates of :func:`check_paper_gates` (DLWA
reduction at 10% >= 80%, wear reduction > 0, execution speedup > 1x,
zero recompiles across repeated dispatches), and the exit code is
theirs.  The figures are simulated statistics of the modelled device,
not timings.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax

from repro.core import headline

# bump when the BENCH_paper.json layout changes
SCHEMA_VERSION = 5

# the paper's summary claims, as floors the artifact must clear
PAPER_DLWA_REDUCTION_FLOOR = 0.80   # paper: 92% at 10% occupancy
PAPER_WEAR_REDUCTION_FLOOR = 0.0    # paper: up to 12% less wear
PAPER_EXEC_SPEEDUP_FLOOR = 1.0      # paper: up to 3.7x faster


def check_paper_gates(artifact: dict) -> int:
    """The acceptance bars over a ``BENCH_paper.json`` artifact.

    Pure function of the artifact dict (no benchmarking) so the gate
    logic is unit-testable: returns 0 when every gate passes, 1
    otherwise, printing one stderr WARNING per failed gate."""
    rc = 0
    dlwa = artifact["dlwa"]["reduction_at_10pct"]
    if dlwa < PAPER_DLWA_REDUCTION_FLOOR:
        print(f"WARNING: DLWA reduction at 10% occupancy {dlwa:.1%} "
              f"below the {PAPER_DLWA_REDUCTION_FLOOR:.0%} floor",
              file=sys.stderr)
        rc = 1
    wear = artifact["wear"]["wear_reduction"]
    if wear <= PAPER_WEAR_REDUCTION_FLOOR:
        print(f"WARNING: silent policy saved no wear "
              f"(wear reduction {wear:.1%})", file=sys.stderr)
        rc = 1
    speedup = artifact["exec"]["speedup"]
    if speedup <= PAPER_EXEC_SPEEDUP_FLOOR:
        print(f"WARNING: workload execution speedup {speedup:.2f}x "
              f"not above the 1x floor", file=sys.stderr)
        rc = 1
    if artifact["recompiles"]["delta_total"] != 0:
        print("WARNING: paper figures recompiled on a repeated "
              "same-shape dispatch", file=sys.stderr)
        rc = 1
    return rc


def _occ_list(text: str):
    try:
        occs = [float(t) for t in text.split(",") if t.strip()]
    except ValueError as exc:
        raise argparse.ArgumentTypeError(
            f"--occupancies expects comma-separated floats, got "
            f"{text!r}") from exc
    if not occs or not all(0.0 < o <= 1.0 for o in occs):
        raise argparse.ArgumentTypeError(
            f"--occupancies values must be in (0, 1], got {text!r}")
    return occs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, allow_abbrev=False)
    ap.add_argument("--occupancies", type=_occ_list,
                    default=list(headline.DEFAULT_OCCUPANCIES),
                    help="DLWA sweep points (comma-separated, in (0,1])")
    ap.add_argument("--zones", type=int, default=4,
                    help="zones per DLWA lane")
    ap.add_argument("--wear-zones", type=int, default=8,
                    help="zones churned by the wear/exec figures")
    ap.add_argument("--wear-cycles", type=int, default=8,
                    help="RESET churn cycles of the wear figure")
    ap.add_argument("--exec-cycles", type=int, default=4,
                    help="churn cycles of the execution-time figure")
    ap.add_argument("--wear-bound", type=int, default=None,
                    help="silent-policy wear-leveling bound in erases "
                         "(default: unbounded)")
    ap.add_argument("--quick", action="store_true",
                    help="small sweep (3 occupancies, 4 zones, "
                         "2 cycles)")
    ap.add_argument("--out", type=str, default="paper_headline.json",
                    help="JSON output path ('' = stdout only)")
    args = ap.parse_args(argv)
    if args.quick:
        args.occupancies = [0.1, 0.3, 0.7]
        args.wear_zones = min(args.wear_zones, 4)
        args.wear_cycles = min(args.wear_cycles, 4)
        args.exec_cycles = min(args.exec_cycles, 2)

    report = headline.paper_report(
        occupancies=args.occupancies, dlwa_zones=args.zones,
        wear_zones=args.wear_zones, wear_cycles=args.wear_cycles,
        exec_cycles=args.exec_cycles, wear_bound=args.wear_bound)
    report["meta"] = {"schema_version": SCHEMA_VERSION,
                      "device": "zn540/superblock",
                      "jax_backend": jax.default_backend(),
                      "quick": bool(args.quick),
                      "occupancies": len(args.occupancies)}

    d, w, x = report["dlwa"], report["wear"], report["exec"]
    print("DLWA vs occupancy (traditional -> silent):")
    for o, t, s, r in zip(d["occupancies"], d["traditional_dlwa"],
                          d["silent_dlwa"], d["dlwa_reduction"]):
        print(f"  occ {o:4.0%}: {t:7.3f} -> {s:6.3f}  (-{r:.1%})")
    print(f"DLWA reduction at 10% occupancy: "
          f"{d['reduction_at_10pct']:.1%} (paper: 92%)")
    print(f"wear: {w['traditional_erases']:.0f} -> "
          f"{w['silent_erases']:.0f} block erases "
          f"(-{w['wear_reduction']:.1%}; paper: up to 12%)")
    print(f"execution: {x['traditional_s']:.3f}s -> "
          f"{x['silent_s']:.3f}s  ({x['speedup']:.2f}x; "
          f"paper: up to 3.7x)")
    print(f"recompiles on repeat: "
          f"{report['recompiles']['delta_total']:.0f}")

    if args.out:
        with open(args.out, "w") as fh:
            fh.write(json.dumps(report, indent=2) + "\n")
        print(f"wrote {args.out}")
    return check_paper_gates(report)


if __name__ == "__main__":
    sys.exit(main())
