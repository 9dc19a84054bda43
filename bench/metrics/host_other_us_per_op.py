"""Readback, row checks and rollups per real op: the benchmark's
``call`` span less every other span inside it (build, record, engine,
timing), over the window, in microseconds per real op.  That is the
decode, ``assert_all_ok``, the rows the user reads, and the replay's
row validation and padding."""

INNER = ("evaluator.build", "record", "fleet.engine", "fleet.timing")


def read(ctx):
    s = ctx["sections"]
    if "call" not in s or not ctx["real_ops"]:
        return None
    rest = s["call"] - sum(s.get(n, 0.0) for n in INNER)
    return rest / ctx["real_ops"] * 1e6
