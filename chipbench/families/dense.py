"""The dense decoder: pre-norm attention and a SwiGLU MLP in every layer.

Semantics, as the configuration states them: decoder-only, pre-norm:
RMSNorm (eps 1e-6, gain) -> attention -> residual; RMSNorm -> SwiGLU MLP
-> residual; final RMSNorm; untied LM head as a float GEMM.  Activations
are bfloat16 between ops; norms, rotary embedding and attention compute
in float32 and round once, the SwiGLU product is bfloat16 arithmetic.
Every projection is an APSQ GEMM and attention reads the INT8 KV cache
(``chipbench.reference``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import counts, weights
from chipbench.reference import (Reference, kv_attention, qgemm, rmsnorm,
                                 rope)

# Mean |x| of the float activation entering the SwiGLU's down projection:
# the product silu(g) * i of two unit normals has mean |.| ~ 0.42.  Every
# other projection sees unit scale (RMS-normed inputs, attention outputs).
MLP_OUT_MEAN_ABS = 0.42


def mean_abs_in(names: list) -> float:
    """Mean |x| of the float input of the linear at a tree path (its key
    names)."""
    if "ffn" in names and "wo" in names:
        return MLP_OUT_MEAN_ABS
    return weights.MEAN_ABS_UNIT


def float_init(names: list, shape: tuple) -> tuple:
    """(mean, std) of a float leaf: norm gains are ones, the embedding
    and LM head uniform at the fan-in scale 1/sqrt(d)."""
    field = names[-1]
    if field == "scale":
        return 1.0, 0.0
    if field == "table":
        return 0.0, 1.0 / math.sqrt(shape[-1])
    if field == "w":
        return 0.0, 1.0 / math.sqrt(shape[0])
    raise ValueError(f"no rule for leaf {'/'.join(names)}")


def deployed_shapes(cfg):
    """Abstract deployed tree: init's tree with each quantized linear
    (``{"w", "qp": QuantState}``) replaced by the program's own export of
    it (shapes only)."""
    from repro.core import QuantState
    from repro.models.model import init_lm
    from repro.quant.export import _export_one

    abstract = jax.eval_shape(lambda k: init_lm(k, cfg),
                              jax.random.PRNGKey(0))

    def walk(t):
        if isinstance(t, dict) and isinstance(t.get("qp"), QuantState):
            fn = _export_one
            if t["qp"].ax.ndim == 1:                  # scan-stacked units
                fn = jax.vmap(_export_one)
            dq, _ = jax.eval_shape(fn, t["w"], t["qp"])
            return {"qp": dq}
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        return t

    return walk(abstract)


def dims(conf: dict) -> dict:
    """Sizes as the benchmark states them (the configuration file)."""
    m = conf["model"]
    return {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "hd": m["head_dim"], "d_ff": m["d_ff"], "vocab": m["vocab"],
            "rope_fraction": m["rope_fraction"],
            "rope_theta": m["rope_theta"], "eps": m["norm_eps"],
            "n_p": conf["quant"]["n_p"]}


def check(dims: dict, cfg) -> None:
    """The program's config must be the one the file states."""
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
           "hd": cfg.hd, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
           "rope_fraction": cfg.rope_fraction, "rope_theta": cfg.rope_theta}
    bad = {k: (v, dims[k]) for k, v in got.items() if v != dims[k]}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def attention(x, codes, d: dict, m: dict, bits: int):
    """x plus the pre-norm attention half of a layer over unit 0's
    ``mix`` linears."""
    T = x.shape[0]
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["hd"]
    pos = jnp.arange(T)
    h = rmsnorm(x, d["eps"])

    def proj(name, a):
        return qgemm(a, codes[f"0.mix.{name}.qp"], m[f"0.mix.{name}.qp"],
                     bits)

    q = proj("wq", h).reshape(T, hq, hd)
    k = proj("wk", h).reshape(T, hkv, hd)
    v = proj("wv", h).reshape(T, hkv, hd)
    q = rope(q, pos, d["rope_fraction"], d["rope_theta"])
    k = rope(k, pos, d["rope_fraction"], d["rope_theta"])
    a = kv_attention(q, k, v, bits).reshape(T, hq * hd)
    return x + proj("wo", a)


@functools.partial(jax.jit, static_argnames=("dims", "meta", "bits"))
def layer(x, codes, *, dims, meta, bits):
    """One decoder layer.  ``meta`` is a tuple of (name, exponents)."""
    d = dict(dims)
    m = {k: dict(v) for k, v in meta}
    x = attention(x, codes, d, m, bits)

    def proj(name, a):
        return qgemm(a, codes[f"0.ffn.{name}.qp"], m[f"0.ffn.{name}.qp"],
                     bits)

    h = rmsnorm(x, d["eps"])
    hh = jax.nn.silu(proj("wg", h)) * proj("wi", h)   # in the activation dtype
    return x + proj("wo", hh)


def reference(key, cfg, dims: dict, abstract, t_pad: int) -> Reference:
    """The plain model, with the weights ``weights.make_params`` gives
    the program, drawn layer by layer."""
    draw = weights.unit_fn(abstract, float_init)
    return Reference(
        layer,
        {"n_units": cfg.n_units, "n_heads": dims["n_heads"],
         "n_kv_heads": dims["n_kv_heads"], "hd": dims["hd"],
         "rope_fraction": dims["rope_fraction"],
         "rope_theta": dims["rope_theta"], "eps": dims["eps"]},
        weights.linear_meta(abstract, mean_abs_in),
        lambda u: draw(key, u),
        weights.float_leaf(key, abstract, float_init, "embed", "table"),
        weights.float_leaf(key, abstract, float_init, "head", "w"),
        t_pad=t_pad)


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------

def linear_shapes(dims: dict) -> list:
    """[(K, N)] of one layer's seven projections."""
    d, hq, hkv, hd, f = (dims["d_model"], dims["n_heads"],
                         dims["n_kv_heads"], dims["hd"], dims["d_ff"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]


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
            + dims["n_layers"] * counts.attn_ops(dims, ctx))


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
    roof = counts.roofline_seconds

    def gemm_roof(m):
        return sum(roof(counts.gemm_ops(m, k, n),
                        counts.gemm_bytes(m, k, n, dims["n_p"]), i8, bw)
                   for k, n in linear_shapes(dims))

    out = {"gemm_s": 0.0, "attn_s": 0.0, "model_ops": 0.0, "tokens": 0}
    if call["kind"] == "decode":
        for ctxs in call["steps"]:
            out["gemm_s"] += L * gemm_roof(call["rows"])
            live = sum(ctxs)
            out["attn_s"] += L * roof(
                counts.attn_ops(dims, live),
                counts.attn_bytes(dims, live, len(ctxs)), bf, bw)
            out["model_ops"] += sum(token_ops(dims, c) for c in ctxs)
            out["tokens"] += len(ctxs)
    else:
        p, c = call["start"], call["chunk"]
        out["gemm_s"] += L * gemm_roof(c)
        live = sum(p + t + 1 for t in range(c))
        out["attn_s"] += L * roof(
            counts.attn_ops(dims, live), counts.attn_bytes(dims, p + c, c),
            bf, bw)
        out["model_ops"] += sum(token_ops(dims, p + t + 1)
                                for t in range(c))
        out["tokens"] += c
    return out
