# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (plus the roofline summary when dry-run results exist).
from __future__ import annotations

import sys

from benchmarks import ckpt_zns, paper_figures, roofline_report
from benchmarks.common import Bench


def main() -> None:
    quick = "--quick" in sys.argv
    b = Bench()
    # the SA<->DLWA trade-off needs enough churn to pressure the
    # active-zone budget; 1M ops is the floor for fig7b/7c
    n_ops = 1_000_000

    b.timeit("fig4a_7a_dlwa_vs_occupancy",
             paper_figures.fig4a_7a_dlwa_vs_occupancy,
             ("reduction_at_10pct", "paper_claim"))
    b.timeit("fig4b_7d_interference",
             paper_figures.fig4b_7d_interference,
             ("worst_baseline", "worst_silentzns"))
    b.timeit("fig7b_sa_dlwa_tradeoff",
             lambda: paper_figures.fig7b_sa_dlwa_tradeoff(n_ops),
             ("dlwa_reduction_at_low_thr", "sa_increase_delaying_finish",
              "paper_sa_increase"))
    b.timeit("fig7c_wear",
             lambda: paper_figures.fig7c_wear(n_ops),
             ("baseline_erases", "silentzns_erases", "erase_reduction"))
    b.timeit("fig7c_wear_leveling", paper_figures.fig7c_wear_leveling,
             ("baseline_max_wear", "silentzns_max_wear",
              "baseline_std", "silentzns_std"))
    b.timeit("fig8_geometry_sweep", paper_figures.fig8_geometry_sweep,
             ("fixed_over_vchunk2_P8S128", "paper_claim"))
    b.timeit("fig9_throughput", paper_figures.fig9_throughput,
             ("peak_P16_1job", "P8_1job", "P8_2jobs"))
    b.timeit("table3_interference", paper_figures.table3_interference,
             ("fixed_minus_vchunk2_multiseg",))
    b.timeit("table4_alloc_latency", paper_figures.table4_alloc_latency,
             ("fixed_us", "superblock_us", "block_us"))
    b.timeit("ckpt_zns_all_archs", ckpt_zns.run_all,
             ("mean_dlwa_reduction", "worst_baseline_dlwa"))

    try:
        s = roofline_report.summary()
        b.add("roofline_dryrun_summary", 0.0,
              ";".join(f"{k}={v}" for k, v in s.items()))
    except Exception as e:  # noqa: BLE001 -- dry-run results may be absent
        b.add("roofline_dryrun_summary", 0.0, f"skipped={e}")

    b.emit()


if __name__ == "__main__":
    main()
