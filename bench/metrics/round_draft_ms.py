"""round_draft_ms: device time of the leaf ops under the ``draft`` scope
(``core/speculative.sd_draft_phase``: the drafter's gamma + 1 passes, their
heads and sampling) per round execution, from the device trace
(``lib.scopes``). Moves tpot_p50_ms."""
from lib import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    return scopes.round_scope_ms(scopes.load(), "draft")
