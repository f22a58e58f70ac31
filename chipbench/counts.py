"""Operations and bytes of the served model's calls, from shapes alone.

The yardstick for the roofline shares and ``step_mfu``.  Conventions:

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

def linear_shapes(dims: dict) -> list:
    """[(K, N)] of one layer's seven projections."""
    d, hq, hkv, hd, f = (dims["d_model"], dims["n_heads"],
                         dims["n_kv_heads"], dims["hd"], dims["d_ff"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]


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


def gemm_params(dims: dict) -> float:
    """Weights of every GEMM a token passes through: the layers' seven
    projections and the LM head."""
    per_layer = sum(k * n for k, n in linear_shapes(dims))
    return float(dims["n_layers"] * per_layer
                 + dims["d_model"] * dims["vocab"])


def token_ops(dims: dict, ctx: int) -> float:
    """Model operations of one processed token that sees ``ctx``
    positions: 2 x GEMM weights + 4 ctx Hq hd per attention layer."""
    return (2.0 * gemm_params(dims)
            + dims["n_layers"] * attn_ops(dims, ctx))


def roofline_seconds(ops: float, byt: float, ops_peak: float,
                     bw_peak: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / ops_peak, byt / bw_peak)


def call_costs(dims: dict, call: dict, peak: dict) -> dict:
    """Least device seconds and model operations of one engine dispatch.

    ``call`` is a dispatch record: ``{"kind": "decode", "rows": B,
    "steps": [[ctx of each emitted token], ...]}`` (one list per scan
    step; a token's ctx is the positions its query sees) or ``{"kind":
    "prefill", "start": p, "chunk": c}``.  Each GEMM call and each
    attention call is bounded on its own (int8 peak for the APSQ GEMMs,
    bf16 peak for the float32 attention, HBM bandwidth for both), and the
    bounds are summed per kernel family."""
    L = dims["n_layers"]
    i8, bf, bw = peak["int8_ops"], peak["bf16_flops"], peak["hbm_bytes_s"]

    def gemm_roof(m):
        return sum(roofline_seconds(gemm_ops(m, k, n),
                                    gemm_bytes(m, k, n, dims["n_p"]), i8, bw)
                   for k, n in linear_shapes(dims))

    out = {"gemm_s": 0.0, "attn_s": 0.0, "model_ops": 0.0, "tokens": 0}
    if call["kind"] == "decode":
        for ctxs in call["steps"]:
            out["gemm_s"] += L * gemm_roof(call["rows"])
            live = sum(ctxs)
            out["attn_s"] += L * roofline_seconds(
                attn_ops(dims, live), attn_bytes(dims, live, len(ctxs)),
                bf, bw)
            out["model_ops"] += sum(token_ops(dims, c) for c in ctxs)
            out["tokens"] += len(ctxs)
    else:
        p, c = call["start"], call["chunk"]
        out["gemm_s"] += L * gemm_roof(c)
        live = sum(p + t + 1 for t in range(c))
        out["attn_s"] += L * roofline_seconds(
            attn_ops(dims, live), attn_bytes(dims, p + c, c), bf, bw)
        out["model_ops"] += sum(token_ops(dims, p + t + 1)
                                for t in range(c))
        out["tokens"] += c
    return out
