"""kv_attn_roofline: the least time of every INT8 KV-attention call the
engine made in the traced window, counting only live cache positions
(each slot's length at each step; the family's ``call_costs`` in
``ctx["call_costs"]``), over the device time of the attention kernel's
events and of the ops that stage its operands (the gathered, head-major
copy of each slot's pages)."""


def read(ctx):
    tr = ctx["trace"]
    t = tr["kernels"].get("kv_attn", 0.0)
    if not t or not ctx["call_costs"]:
        return None
    least = sum(c["attn_s"] for c in ctx["call_costs"])
    return 100.0 * least / (t + tr["staging"].get("kv_attn", 0.0))
