"""round_ms: device time of the chain round program (``sd_round``, run by
``_cached_round_donated``) per execution, from the device trace
(``lib.tracing.round_time``). Moves tpot_p50_ms."""
from lib.tracing import round_time


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = round_time(ctx.trace)
    return 1e3 * seconds / n if n else None
