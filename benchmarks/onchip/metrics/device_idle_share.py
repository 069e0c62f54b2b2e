"""device_idle_share: 1 - (union of device-op intervals / traced window),
from the profiler trace (``trace_reduce.reduce``).  Moves
``search_qps``."""


def read(ctx):
    if ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
