"""idle_share: 1 - (union of device-op intervals / traced window), from the
device trace (TPU v5e). Moves tpot_p90_ms."""
from lib.tracing import busy_s


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(ctx.trace) / ctx.trace.window_s)
