"""Ahead-of-time compiles for one TPU v5e chip, with no chip attached.

The TPU compiler ships with jaxlib's TPU plugin, and it compiles for a
chip that is only described: what it refuses here (a Pallas block the
TPU tiling cannot hold, a loop carry Mosaic cannot legalize) would
otherwise surface only on the chip.  The main path's dispatches are
compiled at the paper's zn540 widths, with few lanes so each compile
stays a few seconds:

* ``run_programs`` on the superblock/block/vchunk2 union engine with a
  per-lane ``DynConfig`` stack (both allocation policies), and the
  grid cell's 384-lane dispatch in the lane groups the TPU runs;
* ``simulate_fleet_ops`` over the same lanes;
* the ``zns_alloc`` Pallas kernel at zn540 and custom16 shapes for the
  three specs (custom16's BLOCK spec splits its 16 groups into blocks).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import engine as E
from repro.core import timing
from repro.core.elements import BLOCK, SUPERBLOCK, vchunk
from repro.core.geometry import ZoneGeometry, custom16, zn540
from repro.kernels.zns_alloc.ops import _pick_group_block
from repro.kernels.zns_alloc.zns_alloc import zns_alloc_pallas

SPECS = (SUPERBLOCK, BLOCK, vchunk(2))
LANES, OPS = 8, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_run_programs_compiles_for_v5e(one_chip):
    flash, zone = zn540()
    eng = E.ZoneEngine(flash, zone, SPECS, max_active=14)
    state = _on(one_chip, jax.eval_shape(lambda: E.init_state(eng.cfg)))
    dyn = _on(one_chip, jax.eval_shape(lambda: E.stack_dyn([
        eng.dyn(spec=SPECS[k % len(SPECS)],
                alloc_policy=("traditional", "silent")[k % 2])
        for k in range(LANES)])))
    programs = jax.ShapeDtypeStruct((LANES, OPS, 5), jnp.int32,
                                    sharding=one_chip)
    compiled = E.run_programs.lower(eng.cfg, state, programs, dyn).compile()
    _, trace = jax.eval_shape(
        lambda s, p, d: E.run_programs(eng.cfg, s, p, d),
        state, programs, dyn)
    assert trace.ok.shape == (LANES, OPS)
    assert trace.elems.shape == (LANES, OPS, eng.cfg.n_slots)
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_grid_dispatch_compiles_in_lane_groups_for_v5e(one_chip):
    """The grid cell's dispatch, 384 lanes x 832 rows, in the lane-group
    form the TPU rule picks at 384 lanes, fits one chip's scratch
    memory with room to spare."""
    flash, zone = zn540()
    eng = E.ZoneEngine(flash, zone, SPECS, max_active=14)
    lanes, ops = 384, 832
    width = E.lane_group_width(lanes, "tpu")
    assert width > 1
    state = _on(one_chip, jax.eval_shape(lambda: E.init_state(eng.cfg)))
    dyn = _on(one_chip, jax.eval_shape(lambda: E.stack_dyn([
        eng.dyn(spec=SPECS[k % len(SPECS)],
                alloc_policy=("traditional", "silent")[k % 2])
        for k in range(lanes)])))
    programs = jax.ShapeDtypeStruct((lanes, ops, 5), jnp.int32,
                                    sharding=one_chip)
    groups = jax.jit(E._run_lane_groups, static_argnums=(0, 4, 5))
    compiled = groups.lower(eng.cfg, state, programs, dyn, None,
                            width).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    _, trace = jax.eval_shape(
        lambda s, p, d: E._run_lane_groups(eng.cfg, s, p, d, None, width),
        state, programs, dyn)
    assert trace.ok.shape == (lanes, ops)


def test_simulate_fleet_ops_compiles_for_v5e(one_chip):
    flash, zone = zn540()
    shapes = (((LANES, OPS, zone.parallelism), jnp.int32),
              ((LANES, OPS), jnp.int32), ((LANES, OPS), jnp.int32),
              ((LANES, OPS), jnp.float32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    timing.simulate_fleet_ops.lower(
        *args, n_luns=flash.n_luns, n_tenants=4).compile()
    done, lat, span = jax.eval_shape(
        lambda *a: timing.simulate_fleet_ops(*a, n_luns=flash.n_luns,
                                             n_tenants=4), *args)
    assert done.shape == lat.shape == (LANES, OPS)
    assert span.shape == (LANES,)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
@pytest.mark.parametrize("device", ["zn540", "custom16"])
def test_zns_alloc_kernel_compiles_for_v5e(one_chip, device, spec):
    flash, zone = (zn540() if device == "zn540"
                   else (custom16(), ZoneGeometry(16, 2)))
    cfg, _ = E.make_config(flash, zone, spec)
    gb = _pick_group_block(cfg.n_groups)
    grid = jax.ShapeDtypeStruct((cfg.n_groups, cfg.per_group), jnp.int32,
                                sharding=one_chip)
    elig = jax.ShapeDtypeStruct((cfg.n_groups,), jnp.int32,
                                sharding=one_chip)
    compiled = zns_alloc_pallas.lower(grid, grid, elig, take=cfg.take,
                                      group_block=gb).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if device == "custom16" and spec is BLOCK:
        assert gb < cfg.n_groups        # the kernel grid is split
