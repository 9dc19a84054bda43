"""Share of the scanned (lane, op) cells that are NOP padding, over the
window's calls, in percent."""


def read(ctx):
    if not ctx["cells"]:
        return None
    return (ctx["cells"] - ctx["real_ops"]) / ctx["cells"] * 100.0
