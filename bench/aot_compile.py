"""Compile each cell's dispatch shapes ahead of time for a described
TPU v5e chip, with no chip attached.

For every cell of ``BENCHMARK.json`` this builds the cell's union engine
from its configuration file and compiles, for one v5e chip:

* ``run_programs`` at the cell's (lanes, padded ops) shape with a
  per-lane ``DynConfig`` stack;
* ``simulate_fleet_ops`` at the same shape.

It prints one JSON line per compile (seconds and the compiled
program's memory analysis).  A compile that passes is a rehearsal, not
a chip run.  Run it on a machine with no TPU::

    JAX_PLATFORMS=cpu python3 bench/aot_compile.py
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def shapes(config, traffic, seed: int = 0):
    """(lanes, padded ops, traffic classes) of one cell's dispatch; a
    fleet cell's first call is built on the host to read its shape."""
    if traffic["driver"] == "fleet":
        from harness import load_module
        from repro.fleet import search

        d = load_module("drivers", "fleet").Driver(config, traffic, seed)
        d.setup()
        programs, _, _ = search.build_fleet_batch(
            d.eng, d._configs(0), n_devices=d.n_devices,
            pad_quantum=d.ev.pad_quantum)
        return programs.shape[0], programs.shape[1], search.N_TENANTS
    return len(traffic["lanes"]), traffic["pad_ops"], len(traffic["classes"])


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import load_cell
    from drivers.fleet import spec_of
    from repro.core import engine as E
    from repro.core import timing
    from repro.core.geometry import FlashGeometry, ZoneGeometry

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        _, _, config, traffic = load_cell(w["name"])
        lanes, ops, n_tenants = shapes(config, traffic)
        flash = FlashGeometry(**config["flash"])
        specs = [spec_of(s) for s in config["specs"]]
        eng = E.ZoneEngine(flash, ZoneGeometry(**config["zone"]), specs,
                           max_active=config["max_active"])
        state = on(jax.eval_shape(lambda: E.init_state(eng.cfg)))
        dyn = on(jax.eval_shape(lambda: E.stack_dyn([
            eng.dyn(spec=specs[k % len(specs)],
                    alloc_policy=("traditional", "silent")[k % 2])
            for k in range(lanes)])))
        programs = jax.ShapeDtypeStruct((lanes, ops, 5), jnp.int32,
                                        sharding=one)
        t0 = time.perf_counter()
        c = E.run_programs.lower(eng.cfg, state, programs, dyn).compile()
        m = c.memory_analysis()
        print(json.dumps({"cell": w["name"], "fn": "run_programs",
                          "shape": [lanes, ops],
                          "compile_s": time.perf_counter() - t0,
                          "argument_bytes": m.argument_size_in_bytes,
                          "output_bytes": m.output_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes}), flush=True)
        args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
            ((lanes, ops, eng.cfg.parallelism), jnp.int32),
            ((lanes, ops), jnp.int32), ((lanes, ops), jnp.int32),
            ((lanes, ops), jnp.float32))]
        t0 = time.perf_counter()
        c = timing.simulate_fleet_ops.lower(
            *args, n_luns=flash.n_luns, n_tenants=n_tenants + 1).compile()
        m = c.memory_analysis()
        print(json.dumps({"cell": w["name"], "fn": "simulate_fleet_ops",
                          "shape": [lanes, ops],
                          "compile_s": time.perf_counter() - t0,
                          "temp_bytes": m.temp_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
