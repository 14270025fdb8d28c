"""round_mfu: operations the traced window's decode rounds need (active
rows only, ``lib.counts.round_flops``) over their device time times the
chip's peak bf16 FLOP/s: the whole round's share of peak. Moves
tpot_p50_ms."""
from lib.counts import round_flops
from lib.tracing import round_time


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    seconds, n = round_time(ctx.trace)
    if not n:
        return None
    t, d = ctx.counts
    flops = sum(round_flops(t, d, rows, ctx.gamma)
                for rows in ctx.traced_rounds) / len(ctx.traced_rounds)
    return 100.0 * flops / (seconds / n * ctx.peaks["flops_bf16"])
