"""step_mfu: model operations of every token processed in the traced
window (prompt and output tokens: 2 x GEMM weights plus 4 ctx Hq hd per
attention layer, ``chipbench.counts.token_ops``) over the window's
length and the chip's int8 peak."""
from chipbench import counts


def read(ctx):
    calls = ctx["calls"]
    if not calls or not ctx["trace"]["n_ops"] or ctx["peak"] is None:
        return None
    ops = sum(counts.call_costs(ctx["dims"], c, ctx["peak"])["model_ops"]
              for c in calls)
    return 100.0 * ops / (ctx["trace"]["window_s"]
                          * ctx["peak"]["int8_ops"])
