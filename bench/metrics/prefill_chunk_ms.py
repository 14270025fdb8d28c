"""prefill_chunk_ms: device time of the prefill-step programs (the donated
``decode_step`` of drafter and target, ``_prefill_one_chunk`` ->
``_cached_decode``) per prefill chunk, from the device trace
(``lib.tracing.prefill_time``). A step that feeds a chunk runs it before
the round, so it moves tpot_p90_ms (and the time to first token)."""
from lib.tracing import host_count, prefill_time


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, _ = prefill_time(ctx.trace)
    chunks = host_count(ctx.trace, "prefill_chunk")
    if chunks == 0 or seconds == 0:
        return None
    return 1e3 * seconds / chunks
