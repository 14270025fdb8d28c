"""round_kv_gather_ms: device time of the leaf ops under the ``kv_gather``
scope (``models/attention.paged_decode_attention``: each row's mapped pages
gathered from the pool, reshaped and cast), of both models, per round
execution, from the device trace (``lib.scopes``). Moves tpot_p50_ms."""
from lib import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    return scopes.round_scope_ms(scopes.load(), "kv_gather")
