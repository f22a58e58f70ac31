"""gemm_staging_ms: device time of the fused decode program
(``_decode_impl``) under the ``apsq_gemm`` scope less that of the APSQ
kernels' own events, in the traced window, over the scan steps it ran
(``chipbench.scopes``).  It covers the projections' APSQ GEMMs only:
activation quantize, the backend call's pads and slices, the int32 ->
float rescale.  Not counted: the layer scan's slicing of the stacked
weights (under the bare ``decode`` scope) and the LM head, a float GEMM
that holds no ``apsq_gemm``."""
from pathlib import Path

from chipbench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    tab = scopes.table(ROOT)
    if tab is None:
        return None
    gemm = scopes.under(tab["programs"].get("_decode_impl", {}),
                        "apsq_gemm")
    kern = scopes.under(tab["kernels"].get("_decode_impl", {})
                        .get("apsq_gemm", {}), "apsq_gemm")
    return scopes.per_decode_step(ctx, gemm - kern)
