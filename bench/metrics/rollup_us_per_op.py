"""Rollups per real op: the program's ``fleet.rollup`` span (the rows
users read: ``config_report``, ``tenant_class_report``,
``pooled_wear``, ``lane_metrics``, with the device reads they make;
nested rollups count once) over the window, in microseconds per real
op."""


def read(ctx):
    s = ctx["sections"].get("fleet.rollup")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
