"""Application recording per real op: the benchmark's ``record`` span
around ``RecordingBackend`` + ``record_lsm`` over the window, in
microseconds per real (replayed) op."""


def read(ctx):
    s = ctx["sections"].get("record")
    if s is None or not ctx["real_ops"]:
        return None
    return s / ctx["real_ops"] * 1e6
