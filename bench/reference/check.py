"""The plain reference of a batch of lanes, and the comparisons that
decide ``correct``.

``run_reference`` replays every lane's op rows through the numpy device
model (:mod:`.model`) and prices them on the numpy busy clock
(:mod:`.clock`), from the rows and the configuration alone.  The
rollups below recompute, from those reference arrays, the rows a user
reads: the fleet search's per-config row and the per-class and
per-lane report of a replay.  Each ``count_*`` returns how many values
of the program's output differ from the reference; every limit is 0.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import clock
from .model import OP_NOP, replay_lane
from .static import Static, real_cells

STATE_FIELDS = ("elem_wear", "elem_avail", "elem_pages", "elem_zone",
                "zone_state", "zone_wp", "zone_host_wp", "zone_elems",
                "zone_cols", "rr_next", "n_active", "host_pages",
                "dummy_pages", "block_erases")
DELTAS = ("ok", "host_delta", "dummy_delta", "erase_delta")


def run_reference(static: Static, flash, programs: np.ndarray,
                  values: Sequence[Dict], parity_tenant: int,
                  dtype=np.float32) -> Dict:
    """Model + clock over ``(L, n_ops, 5)`` rows, one values dict per
    lane; the clock in ``dtype`` (float32 as the simulator states)."""
    lanes = [replay_lane(static, v, p) for v, p in zip(values, programs)]
    out = {k: np.stack([r[k] for r in lanes])
           for k in DELTAS + ("pages", "cols")}
    out["states"] = [r["state"] for r in lanes]
    out["t_page"] = clock.page_times(programs, flash)
    out["n_luns"] = flash.n_luns
    done, lat, span = clock.busy_clock(
        out["cols"], out["pages"], programs[:, :, 4], out["t_page"],
        flash.n_luns, parity_tenant + 1, dtype=dtype)
    out["completions"] = done.astype(np.float32)
    out["latencies"] = lat.astype(np.float32)
    out["makespans"] = span.astype(np.float32)
    out["programs"] = np.asarray(programs)
    out["mask"] = np.stack([real_cells(static, v) for v in values])
    out["parity_tenant"] = parity_tenant
    return out


def count_op_rows(got: np.ndarray, want: Sequence[np.ndarray]) -> int:
    """Op rows of the program's batch that differ from the reference's
    lanes, both NOP-padded to the longer of the two; a lane one side
    lacks counts each of its rows."""
    got = np.asarray(got)
    n = max([got.shape[1]] + [len(w) for w in want])
    a = np.zeros((max(len(got), len(want)), n, 5), dtype=np.int64)
    b = np.zeros_like(a)
    a[: len(got), : got.shape[1]] = got[:, :, :5]
    for i, w in enumerate(want):
        b[i, : len(w)] = w
    return int((a != b).any(axis=2).sum())


def count_deltas(got: Dict, ref: Dict, lanes) -> int:
    """Per-op legality bits and host/dummy/erase deltas that differ."""
    return int(sum((np.asarray(got[k])[lanes] != ref[k]).sum()
                   for k in DELTAS))


def count_states(got_states: Dict[str, np.ndarray], ref: Dict, lanes,
                 n_elements: int) -> int:
    """(lane, state field) pairs of the final device state that differ."""
    bad = 0
    for j, lane in enumerate(lanes):
        for f in STATE_FIELDS:
            g = np.asarray(got_states[f][lane])
            if f.startswith("elem_"):
                g = g[:n_elements]
            bad += int(not np.array_equal(g, ref["states"][j][f]))
    return bad


def count_clock(got: Dict, ref: Dict, lanes) -> int:
    """Completions, latencies and makespans that differ in any bit."""
    bad = 0
    for k in ("completions", "latencies", "makespans"):
        g = np.asarray(got[k], np.float32)[lanes]
        bad += int((g.view(np.int32)
                    != ref[k].astype(np.float32).view(np.int32)).sum())
    return bad


def count_rows(got_rows: Sequence[Dict], ref_rows: Sequence[Dict]) -> int:
    """Values of the rows a user reads that differ from the reference."""
    bad = 0
    for g, r in zip(got_rows, ref_rows):
        for k, v in r.items():
            if isinstance(v, dict):
                bad += count_rows([g.get(k, {})], [v])
            elif g.get(k) != v:
                bad += 1
    return bad + abs(len(got_rows) - len(ref_rows))


def pooled_wear(ref: Dict, idx) -> np.ndarray:
    """Element wear pooled over reference lanes ``idx`` (their own
    elements only)."""
    return np.concatenate([
        np.asarray(ref["states"][j]["elem_wear"], np.int64)[ref["mask"][j]]
        for j in idx])


def config_row(ref: Dict, idx, n_tenants: int) -> Dict[str, float]:
    """One fleet config's row over its member lanes ``idx``."""
    idx = np.asarray(idx)
    t = ref["programs"][idx][:, :, 4]
    host_d = ref["host_delta"][idx]
    par_t = ref["parity_tenant"]
    host = int(host_d[t != par_t].sum())
    par = int(host_d[t == par_t].sum())
    dummy = int(ref["dummy_delta"][idx].sum())
    wear = pooled_wear(ref, idx)
    mean_w = float(wear.mean()) if wear.size else 0.0
    lat = ref["latencies"][idx].reshape(-1)
    act = ref["pages"][idx].reshape(-1) > 0
    tr = t.reshape(-1)
    p99 = [float(np.percentile(lat[act & (tr == k)], 99))
           if (act & (tr == k)).any() else 0.0 for k in range(n_tenants)]
    return {
        "host_pages": float(host),
        "parity_pages": float(par),
        "dummy_pages": float(dummy),
        "dlwa": (host + par + dummy) / host if host else 1.0,
        "block_erases": float(int(ref["erase_delta"][idx].sum())),
        "max_wear": float(wear.max()) if wear.size else 0.0,
        "wear_cv": float(wear.std() / mean_w) if mean_w > 0 else 0.0,
        "p99_latency_s": max(p99) if p99 else 0.0,
        "makespan_s": float(ref["makespans"][idx].max()),
        "ops_ok": float(ref["ok"][idx].sum()),
    }


def class_report(ref: Dict, names: Sequence[str]) -> Dict[str, Dict]:
    """Per-traffic-class ops, pages and closed-loop latency over all
    reference lanes."""
    t = ref["programs"][:, :, 4].reshape(-1)
    lat = ref["latencies"].reshape(-1)
    pages = ref["pages"].reshape(-1)
    host = ref["host_delta"].reshape(-1)
    act = (ref["programs"][:, :, 0].reshape(-1) != OP_NOP) & \
        ref["ok"].reshape(-1)
    out: Dict[str, Dict] = {}
    for k, name in enumerate(names):
        sel = act & (t == k)
        if not sel.any():
            out[name] = {"ops": 0.0, "pages": 0.0, "host_pages": 0.0,
                         "mean_latency_s": 0.0, "p50_latency_s": 0.0,
                         "p99_latency_s": 0.0, "max_latency_s": 0.0,
                         "p99_over_p50": 0.0}
            continue
        l_k = lat[sel]
        p50 = float(np.percentile(l_k, 50))
        p99 = float(np.percentile(l_k, 99))
        out[name] = {
            "ops": float(sel.sum()),
            "pages": float(pages[sel].sum()),
            "host_pages": float(host[sel].sum()),
            "mean_latency_s": float(l_k.mean()),
            "p50_latency_s": p50,
            "p99_latency_s": p99,
            "max_latency_s": float(l_k.max()),
            "p99_over_p50": p99 / p50 if p50 > 0 else 0.0,
        }
    return out


def lane_row(ref: Dict, j: int) -> Dict[str, float]:
    """One replay lane's DLWA, erases and wear."""
    s = ref["states"][j]
    host, dummy = int(s["host_pages"]), int(s["dummy_pages"])
    wear = pooled_wear(ref, [j])
    mean_w = float(wear.mean()) if wear.size else 0.0
    return {"host_pages": float(host), "dummy_pages": float(dummy),
            "dlwa": (host + dummy) / host if host else 1.0,
            "block_erases": float(int(s["block_erases"])),
            "max_wear": float(wear.max()) if wear.size else 0.0,
            "wear_cv": float(wear.std() / mean_w) if mean_w > 0 else 0.0}


def count_legacy(flash, zone, specs_by_lane, lane_values, programs,
                 got_states: Dict[str, np.ndarray], lanes: List[int],
                 max_active: int, grid_per_group: int) -> int:
    """Lanes checked against the per-op legacy device: host pages,
    dummy pages and DLWA on every lane, and block erases and per-element
    wear on traditional lanes (the legacy device has no silent
    allocator).  Returns the values that differ."""
    from .geometry import ZoneGeometry
    from .legacy import replay

    bad = 0
    for lane in lanes:
        v = lane_values[lane]
        spec = specs_by_lane[lane]
        n_seg = v["zone_pages"] // (zone.parallelism * flash.pages_per_block)
        leg = replay(flash, ZoneGeometry(zone.parallelism, n_seg), spec,
                     v["wear_aware"], max_active, programs[lane])
        host = int(got_states["host_pages"][lane])
        dummy = int(got_states["dummy_pages"][lane])
        bad += int(host != leg.host_pages) + int(dummy != leg.dummy_pages)
        bad += int(((host + dummy) / host if host else 1.0) != leg.dlwa)
        if v["alloc_policy"] == 0:
            bad += int(int(got_states["block_erases"][lane])
                       != leg.block_erases)
            pg = v["per_group"]
            ids = np.arange(leg.elem_wear.size)
            grid = (ids // pg) * grid_per_group + ids % pg
            wear = np.asarray(got_states["elem_wear"][lane])[grid]
            bad += int((wear != leg.elem_wear).sum())
    return bad
