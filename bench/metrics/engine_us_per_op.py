"""Engine dispatch per real op: the program's ``fleet.engine`` section
(transfer, ``run_programs`` scan, block) over the window, in
microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("fleet.engine")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
