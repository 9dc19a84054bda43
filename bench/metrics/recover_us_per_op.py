"""Recovery rollups per real op: the program's ``fleet.recover`` span
inside ``fleet.rollup`` (a failed array's time to recover, rebuilt
pages, tenants' p99 after the failure and per-member DLWA) over the
window, in microseconds per real op.  Nothing where no config fails."""


def read(ctx):
    s = ctx["sections"].get("fleet.recover")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
