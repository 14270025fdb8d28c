"""round_head_ms: device time of the leaf ops under the ``lm_head`` scope
(``models/transformer.logits_from_hidden``) or the ``sampling`` scope
(``core/sampling``: softmax, draws, the residual) per round execution, both
models, from the device trace (``lib.scopes``). Moves tpot_p50_ms."""
from lib import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    return scopes.round_scope_ms(scopes.load(), "lm_head", "sampling")
