"""round_verify_ms: device time of the leaf ops under the ``verify`` scope
(``core/speculative.sd_verify_phase``: the target's pass over gamma + 1
positions, its head and the softmax) per round execution, from the device
trace (``lib.scopes``). Moves tpot_p50_ms."""
from lib import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    return scopes.round_scope_ms(scopes.load(), "verify")
