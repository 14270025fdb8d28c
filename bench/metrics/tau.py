"""tau: tokens committed per row per decode round, pooled over the counted
requests' ``SDStats`` (core/metrics.py). Moves tpot_p50_ms."""


def read(ctx):
    tokens = sum(ctx.stats[rid].sd.total_tokens for rid in ctx.rec.counted)
    blocks = sum(ctx.stats[rid].sd.num_blocks for rid in ctx.rec.counted)
    return tokens / blocks if blocks else None
