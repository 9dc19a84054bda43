"""Lane op rows per real op: the program's ``build.lanes`` span inside
``evaluator.build`` (``build_fleet_batch``'s tenant mixes and merge,
fidelity cut, RAID striping, pad lanes and ``pad_programs``) over the
window, in microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("build.lanes")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
