"""Timing per real op: the program's ``fleet.timing`` section (the
trace's round trip through the host and ``simulate_fleet_ops``) over
the window, in microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("fleet.timing")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
