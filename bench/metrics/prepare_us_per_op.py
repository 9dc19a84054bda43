"""Replay preparation per real op: the program's ``replay.prepare`` span
in ``replay_recorders`` before the dispatch (each recorder's
``program()``, ``validate_rows``, ``pad_programs`` and the
``stack_dyn`` of the lanes' dyns) over the window, in microseconds per
real op."""


def read(ctx):
    s = ctx["sections"].get("replay.prepare")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
