"""round_idle_ms: device-idle time inside the engine's ``engine_step`` host
spans and outside its ``round_wait`` / ``prefill_wait`` spans, cut at the
span edges, per ``decode_round`` span in the traced window
(``lib.scopes.round_idle_ms``): the host work of a step that leaves the
device waiting. Moves tpot_p50_ms."""
from lib import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    return scopes.round_idle_ms(scopes.load())
