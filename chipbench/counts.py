"""Operations and bytes of one kernel call, from shapes alone.

The yardstick for the roofline shares and ``step_mfu``: a family's
``call_costs`` (``chipbench/families``) sums these over the calls of one
engine dispatch.  Conventions:

* a GEMM of [M, K] x [K, N] is 2 M K N operations and moves its INT8
  weights once (K N bytes), its INT8 activation codes (M K), its INT32
  result (4 M N) and its partial-sum exponents (4 n_p N);
* attention counts only *live* cache positions: a query row that sees L
  positions is 4 L Hq hd operations (scores and weighted sum), and a
  call reads each live K and V code once (2 L Hkv hd bytes per sequence)
  and its float32 query and output rows.
  Positions past a slot's length, which the kernel pads up to the page
  table's width, are not counted, so a kernel that stops reading them
  gains share instead of losing it.
"""
from __future__ import annotations


def gemm_ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, n_p: int) -> float:
    return float(k * n + m * k + 4 * m * n + 4 * n_p * n)


def attn_ops(dims: dict, live: int) -> float:
    """Operations of query rows that see ``live`` positions in total."""
    return 4.0 * live * dims["n_heads"] * dims["hd"]


def attn_bytes(dims: dict, kv_tokens: int, q_rows: int) -> float:
    """Bytes of one layer's attention call: ``kv_tokens`` live positions
    read once (K and V codes) and ``q_rows`` float32 query and output
    rows."""
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["hd"]
    return float(2 * kv_tokens * hkv * hd + 2 * 4 * q_rows * hq * hd)


def roofline_seconds(ops: float, byt: float, ops_peak: float,
                     bw_peak: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / ops_peak, byt / bw_peak)
