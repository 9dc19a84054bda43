"""Device idle share of the traced calls: 1 - (union of device
operation intervals / traced window), in percent, from the profiler
trace."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
