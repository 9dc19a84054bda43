"""The closed-loop busy clock of the fleet timing model, in numpy.

An op holds all of its zone's LUN columns for ``ceil(pages / P) *
t_page`` and starts once they are free and its tenant's previous op is
done.  The simulator states this clock in float32; ``dtype`` lets the
benchmark's control compute it one precision lower.
"""

from __future__ import annotations

import numpy as np


def busy_clock(cols, pages, tenants, t_page, n_luns: int, n_tenants: int,
               dtype=np.float32):
    """Completions, latencies ``(L, n_ops)`` and makespans ``(L,)``."""
    n_lanes, n_ops, p = cols.shape
    rows = np.arange(n_lanes)
    lun_free = np.zeros((n_lanes, n_luns), dtype)
    ten_done = np.zeros((n_lanes, n_tenants), dtype)
    done = np.zeros((n_lanes, n_ops), dtype)
    lat = np.zeros((n_lanes, n_ops), dtype)
    t_page = np.asarray(t_page).astype(dtype)
    for i in range(n_ops):
        act = pages[:, i] > 0
        c, t = cols[:, i], tenants[:, i]
        dur = ((pages[:, i] + p - 1) // p).astype(dtype) * t_page[:, i]
        prev = ten_done[rows, t]
        d = (np.maximum(lun_free[rows[:, None], c].max(axis=1), prev)
             + dur).astype(dtype)
        done[act, i] = d[act]
        lat[act, i] = (d - prev)[act]
        lun_free[rows[act, None], c[act]] = d[act, None]
        ten_done[rows[act], t[act]] = d[act]
    return done, lat, lun_free.max(axis=1)


def page_times(programs, flash) -> np.ndarray:
    """Per-op page service time: reads pay t_read + t_xfer, every other
    page-moving op t_prog + t_xfer (float32, as the simulator states)."""
    op = np.asarray(programs)[:, :, 0]
    return np.where(op == 5, np.float32(flash.t_read + flash.t_xfer),
                    np.float32(flash.t_prog + flash.t_xfer))
