"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``reduce_trace_dir`` reads the newest ``.xplane.pb`` of a trace with
nothing but JAX and returns, for the device planes (``/device:TPU:<n>``):

* ``busy_s``: the union of the intervals in which an operation ran on
  the device (line ``XLA Ops``), averaged over the devices;
* ``window_s``: the traced window, from the first to the last event of
  the benchmark's own host spans (or of the device, where there are
  none);
* ``device_ops``: the 10 operation names with the most device time,
  each summed over its own events (a loop's time holds its body's);
* ``idle_gaps``: the 10 longest gaps between device operations, each
  named by the innermost benchmark span that covers its midpoint.

Host spans are the ``jax.profiler.TraceAnnotation`` events whose names
start with ``span_prefix``; they share the device's clock in the trace.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]


def union_ns(intervals: Sequence[Interval]) -> int:
    """Total length of the union of ``[start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Interval], lo: int, hi: int
            ) -> List[Interval]:
    """The gaps inside ``[lo, hi)`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost (shortest) span covering the gap's midpoint."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside every span"


def reduce_events(device_ops: Dict[str, List[Tuple[str, int, int]]],
                  spans: Sequence[Tuple[str, int, int]]) -> Optional[Dict]:
    """The reduction over already-extracted events.

    ``device_ops`` maps a device plane's name to its ``(op name, start
    ns, end ns)`` events; ``spans`` are the benchmark's host spans.
    Returns None when no device ran an operation."""
    if not any(device_ops.values()):
        return None
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
    else:
        lo = min(s for evs in device_ops.values() for _, s, _ in evs)
        hi = max(e for evs in device_ops.values() for _, _, e in evs)
    busy, per_op, gaps = [], {}, []
    for evs in device_ops.values():
        iv = [(max(s, lo), min(e, hi)) for _, s, e in evs
              if e > lo and s < hi]
        busy.append(union_ns(iv))
        for name, s, e in evs:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0) + (min(e, hi)
                                                      - max(s, lo))
        gaps += gaps_ns(iv, lo, hi)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top_ops = sorted(per_op.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "n_devices": len(device_ops),
        "device_ops": [[n, ns / 1e9] for n, ns in top_ops[:10]],
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in gaps[:10]],
    }


def read_xplane(path: str, span_prefix: str
                ) -> Tuple[Dict[str, List[Tuple[str, int, int]]],
                           List[Tuple[str, int, int]]]:
    """Device op events per device plane, and the host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[
                len("/device:TPU:"):].isdigit():
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    # an event's name is its whole HLO instruction;
                    # keep the instruction's own name
                    evs += [(e.name.split(" = ", 1)[0], int(e.start_ns),
                             int(e.end_ns)) for e in line.events]
            device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.name[len(span_prefix):],
                                      int(e.start_ns), int(e.end_ns)))
    return device_ops, spans


def reduce_trace_dir(trace_dir: str, span_prefix: str) -> Optional[Dict]:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    device_ops, spans = read_xplane(path, span_prefix)
    out = reduce_events(device_ops, spans)
    if out is not None:
        out["xplane_bytes"] = os.path.getsize(path)
        out["n_device_events"] = sum(len(v) for v in device_ops.values())
    return out
