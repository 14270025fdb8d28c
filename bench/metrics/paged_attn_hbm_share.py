"""paged_attn_hbm_share: the paged attention kernel's share of its HBM
roofline in the traced window's decode rounds: the live K/V bytes that the
rounds' kernels must read (``kv_bytes``) over the device time under scope
``paged_kernel`` per round (``lib/scopes.py``) times the chips' peak HBM
bandwidth. Across chips both count every chip, so the share is one chip's
where the heads split evenly. Moves tpot_p50_ms."""
from lib import scopes


def kv_bytes(t, d, rows, gamma: int) -> int:
    """Live K/V bytes one round's paged kernels read, with active rows at
    committed lengths ``rows``: each row's K/V at the served pools' bytes
    per token (``lib.counts.ModelCounts``, every chip's heads), once for
    the target's verify pass and once for each of the drafter's gamma + 1
    passes."""
    return sum(rows) * (t.kv_bytes_per_token
                        + (gamma + 1) * d.kv_bytes_per_token)


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    ms = scopes.round_scope_ms(scopes.load(), "paged_kernel")
    if not ms or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    t, d = ctx.counts
    nbytes = sum(kv_bytes(t, d, rows, ctx.gamma)
                 for rows in ctx.traced_rounds) / len(ctx.traced_rounds)
    return 100.0 * nbytes / (ms / 1e3 * ctx.peaks["hbm_bytes_per_s"])
