"""Device busy time per real op of the traced calls: the union of
device operation intervals over the real ops those calls ran, in
microseconds.  It holds whatever jit a later change fuses or renames."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["real_ops"]:
        return None
    return t["busy_s"] / t["real_ops"] * 1e6
