"""round_collective_ms: device time of the decode round's collective ops
(the all-reduces and all-gathers that tensor parallelism puts between the
chips; ``lib/collectives.py``), on the first chip, per round execution.
Moves tpot_p50_ms."""
from lib import collectives


def read(ctx):
    if ctx.trace is None:
        return None
    return collectives.round_collective_ms(collectives.load())
