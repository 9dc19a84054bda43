"""Multi-tenant fleet simulation + allocator search over the ZoneEngine.

The fleet layer turns the repo from "replay the paper's sweeps" into
"search the design space the paper argues for":

* :mod:`repro.fleet.tenants` -- tenant-tagged width-5 op programs, the
  round-robin tenant interleaver, and the program-space RAID striper
  (same stripe math as :class:`repro.array.ZNSArray`);
* :mod:`repro.fleet.runner`  -- T tenants x N devices x K configs
  executed through ONE batched ``run_programs`` dispatch (heterogeneous
  per-lane geometries/allocators *and element specs* via ``DynConfig``
  on a padded union config) plus op-granular fleet timing;
* :mod:`repro.fleet.search`  -- the :class:`SearchSpace` candidate
  codec and the shared batched :class:`Evaluator` (one dispatch per
  candidate set, fidelity-truncated programs, budget ledger), plus
  grid/random enumeration over (tenant mix, zone geometry, chunk size,
  parity, wear-awareness, element spec) scored on a weighted (DLWA,
  wear spread, p99 tenant latency) objective, with the Pareto front of
  non-dominated configs;
* :mod:`repro.fleet.evolve`  -- the adaptive strategy: evolutionary
  proposals (mutation/crossover on the gene vector) with a
  successive-halving rung schedule, a persistent cross-generation
  Pareto archive, and seeded determinism.

Entry points: ``benchmarks/fleet_search.py --strategy {grid,random,
evolve}`` (the sweep), ``examples/fleet.py`` (a small demo).
"""

from repro.fleet.evolve import (EvolveParams, EvolveResult, evolve,
                                evolve_vs_random)
from repro.fleet.runner import (FleetResult, Rebuilds, assert_all_ok,
                                config_report, dispatch_cost,
                                real_op_count, run_fleet)
from repro.fleet.search import (MIXES, N_TENANTS, OBJECTIVE_KEYS,
                                Evaluator, FleetBatch, FleetConfig,
                                SearchSpace, build_fleet_batch,
                                evaluate_configs, fleet_batch,
                                grid_space, pareto_front, random_space,
                                run_configs_legacy, score_rows)
from repro.fleet.tenants import (TENANT_COL, interleave_tenants,
                                 pad_programs, stripe_program, tag_tenant)

__all__ = [
    "EvolveParams", "EvolveResult", "evolve", "evolve_vs_random",
    "FleetResult", "Rebuilds", "assert_all_ok", "config_report",
    "dispatch_cost", "real_op_count", "run_fleet",
    "MIXES", "N_TENANTS", "OBJECTIVE_KEYS", "Evaluator", "FleetBatch",
    "FleetConfig", "SearchSpace", "build_fleet_batch", "evaluate_configs",
    "fleet_batch", "grid_space",
    "pareto_front", "random_space", "run_configs_legacy", "score_rows",
    "TENANT_COL", "interleave_tenants", "pad_programs",
    "stripe_program", "tag_tenant",
]
