"""apsq_gemm_roofline: the least time of every APSQ GEMM call the engine
made in the traced window (each call's max(ops / int8 peak, bytes / HBM
bandwidth), the family's ``call_costs`` in ``ctx["call_costs"]``) over
the device time of the APSQ kernels' events and of the ops that stage
their operands (the weight slab sliced out of the stacked layers and
padded to the kernel's block, the quantized activations): XLA may place
a staged operand in VMEM, and then the kernel's own events leave out its
HBM reads."""


def read(ctx):
    tr = ctx["trace"]
    t = tr["kernels"].get("apsq_gemm", 0.0)
    if not t or not ctx["call_costs"]:
        return None
    least = sum(c["gemm_s"] for c in ctx["call_costs"])
    return 100.0 * least / (t + tr["staging"].get("apsq_gemm", 0.0))
