"""decode_occupancy: decode tokens emitted in the window over the slot
steps the fused decode ran (device steps x max_batch), from the
engine's counters.  A request's first token comes from its prefill and
is not a decode token."""


def read(ctx):
    w = ctx["window"]
    steps = w["counters"]["decode_device_steps"]
    if not steps:
        return None
    return 100.0 * w["decode_tokens"] / (steps * w["max_batch"])
