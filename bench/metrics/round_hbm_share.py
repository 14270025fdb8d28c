"""round_hbm_share: bytes the traced window's decode rounds need
(``lib.counts.round_bytes``: weights, drafter passes and live K/V) over
their device time times the chip's peak HBM bandwidth. Moves
tpot_p50_ms."""
from lib.counts import round_bytes
from lib.tracing import round_time


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    seconds, n = round_time(ctx.trace)
    if not n:
        return None
    t, d = ctx.counts
    nbytes = sum(round_bytes(t, d, rows, ctx.gamma)
                 for rows in ctx.traced_rounds) / len(ctx.traced_rounds)
    return 100.0 * nbytes / (seconds / n * ctx.peaks["hbm_bytes_per_s"])
