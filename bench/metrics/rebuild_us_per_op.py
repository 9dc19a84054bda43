"""Rebuild planning and merging per real op: the program's
``build.rebuild`` span inside ``build.lanes`` (a failed member's
rebuild plan, and its merge with the foreground on every member lane)
over the window, in microseconds per real op.  Nothing where no
config fails."""


def read(ctx):
    s = ctx["sections"].get("build.rebuild")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
