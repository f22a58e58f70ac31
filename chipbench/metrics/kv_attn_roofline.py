"""kv_attn_roofline: the least time of every INT8 KV-attention call the
engine made in the traced window, counting only live cache positions
(each slot's length at each step), over the device time of the
attention kernel's events and of the ops that stage its operands (the
gathered, head-major copy of each slot's pages)."""
from chipbench import counts


def read(ctx):
    tr = ctx["trace"]
    t = tr["kernels"].get("kv_attn", 0.0)
    if not t or not ctx["calls"] or ctx["peak"] is None:
        return None
    least = sum(counts.call_costs(ctx["dims"], c, ctx["peak"])["attn_s"]
                for c in ctx["calls"])
    return 100.0 * least / (t + tr["staging"].get("kv_attn", 0.0))
