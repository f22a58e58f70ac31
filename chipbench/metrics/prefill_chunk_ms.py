"""prefill_chunk_ms: device time of the prefill chunk program
(``_prefill_chunk_impl``) in the traced window, per dispatch."""


def read(ctx):
    n, s = ctx["trace"]["modules"].get("_prefill_chunk_impl", (0, 0.0))
    k = ctx["trace_counters"]["prefill_dispatches"]
    if not n or not k:
        return None
    return 1e3 * s / k
