"""Host program building per real op: the program's ``evaluator.build``
section (``build_fleet_batch``: tenant mixes, striping, per-lane
``DynConfig``s) over the window, in microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("evaluator.build")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
