"""Row checks per real op: the program's ``fleet.check`` span
(``assert_all_ok``'s legality check on the real ops) over the window,
in microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("fleet.check")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
