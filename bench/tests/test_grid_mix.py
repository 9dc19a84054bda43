"""The grid cell's op rows at the configuration's own size: the
program's fleet builder and the plain reference (its own recorder and
striping) give the same rows, lane for lane, and every seed scans the
same real rows in another order of the configs."""

import time

import numpy as np

import harness
from reference.check import count_op_rows


def test_builder_rows_equal_the_reference_rows():
    from repro.fleet import search

    _, _, config, traffic = harness.load_cell("array4.grid96")
    d = harness.load_module("drivers", "fleet").Driver(config, traffic,
                                                      3000000007)
    t0 = time.perf_counter()
    d.setup()
    print(f"set-up {time.perf_counter() - t0:.2f} s")
    orders, real = [], []
    for index in (0, 1):
        configs = d._configs(index)
        orders.append([fc.describe() for fc in configs])
        t0 = time.perf_counter()
        programs, _, _ = search.build_fleet_batch(
            d.eng, configs, n_devices=d.n_devices,
            pad_quantum=d.ev.pad_quantum)
        t1 = time.perf_counter()
        want = d.reference_lanes(configs)
        print(f"build {t1 - t0:.2f} s, reference "
              f"{time.perf_counter() - t1:.2f} s, shape {programs.shape}")
        assert programs.shape[0] == len(want) == 384
        assert count_op_rows(programs, want) == 0
        real.append(int((programs[:, :, 0] != 0).sum()))
        assert real[-1] == sum(len(w) for w in want)
    print(f"real ops per call {real}")
    assert real[0] == real[1]
    assert orders[0] != orders[1] and sorted(orders[0]) == sorted(orders[1])
    assert np.all([len(set(o)) == 96 for o in orders])
