"""Per-lane ``DynConfig``s per real op: the program's ``build.dyn`` span
inside ``evaluator.build`` (every lane's ``eng.dyn(...)`` and the
``stack_dyn`` of them) over the window, in microseconds per real op."""


def read(ctx):
    s = ctx["sections"].get("build.dyn")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
