"""step_mfu: model operations of every token processed in the traced
window (prompt and output tokens; for the dense family 2 x GEMM weights
plus 4 ctx Hq hd per attention layer, from the family's ``call_costs``
in ``ctx["call_costs"]``) over the window's length and the chip's int8
peak."""


def read(ctx):
    costs = ctx["call_costs"]
    if not costs or not ctx["trace"]["n_ops"]:
        return None
    ops = sum(c["model_ops"] for c in costs)
    return 100.0 * ops / (ctx["trace"]["window_s"]
                          * ctx["peak"]["int8_ops"])
