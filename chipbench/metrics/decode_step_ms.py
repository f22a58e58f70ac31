"""decode_step_ms: device time of the fused decode program
(``_decode_impl``) in the traced window, over the scan steps it ran."""


def read(ctx):
    n, s = ctx["trace"]["modules"].get("_decode_impl", (0, 0.0))
    steps = ctx["trace_counters"]["decode_device_steps"]
    if not n or not steps:
        return None
    return 1e3 * s / steps
