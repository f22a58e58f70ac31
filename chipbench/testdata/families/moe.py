"""A sparse-expert family, given to the tests as a new file.

The dense family's attention half, and in place of its MLP the
program's routed SwiGLU experts (``models/moe.py``): float32 router
logits, softmax, the top-k gates renormalized to sum to 1, and each
chosen expert an APSQ SwiGLU (its ``qp_wg``, ``qp_wi``, ``qp_wo`` banks
of per-expert codes) whose outputs are summed by gate.  The reference
routes every token to its top-k experts; ``check`` holds the
configuration to a capacity that never drops one (capacity_factor at
least n_experts / top_k).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import counts, weights
from chipbench.families import dense
from chipbench.reference import HI, Reference, qgemm, rmsnorm

float_init = dense.float_init


def dims(conf: dict) -> dict:
    m = conf["model"]
    return dict(dense.dims(conf), n_experts=m["n_experts"],
                top_k=m["top_k"], capacity_factor=m["capacity_factor"])


def check(dims: dict, cfg) -> None:
    dense.check(dims, cfg)
    got = (cfg.n_experts, cfg.top_k, cfg.capacity_factor)
    want = (dims["n_experts"], dims["top_k"], dims["capacity_factor"])
    if got != want:
        raise ValueError(f"program experts differ from the file: {got}")
    if cfg.capacity_factor * cfg.top_k < cfg.n_experts:
        raise ValueError("a capacity that can drop tokens")


def deployed_shapes(cfg):
    """``dense.deployed_shapes``, and each expert container
    (``{"wi": [U, E, K, N], "qp_wi": QuantState, ...}``) exported per
    unit and expert, its float banks dropped."""
    from repro.core import QuantState
    from repro.quant.export import _export_one

    per_expert = jax.vmap(jax.vmap(_export_one, in_axes=(0, None)))

    def walk(t):
        if not isinstance(t, dict):
            return t
        banks = [k[3:] for k, v in t.items()
                 if k.startswith("qp_") and isinstance(v, QuantState)]
        out = {}
        for k, v in t.items():
            if k in banks:
                continue
            if k[3:] in banks:
                v = jax.eval_shape(per_expert, t[k[3:]], v)[0]
            out[k] = walk(v)
        return out

    return walk(dense.deployed_shapes(cfg))


def mean_abs_in(names: list) -> float:
    if "ffn" in names and names[-1] == "qp_wo":
        return dense.MLP_OUT_MEAN_ABS
    return dense.mean_abs_in(names)


@functools.partial(jax.jit, static_argnames=("dims", "meta", "bits"))
def layer(x, vals, *, dims, meta, bits):
    d = dict(dims)
    m = {k: dict(v) for k, v in meta}
    x = dense.attention(x, vals, d, m, bits)
    h = rmsnorm(x, d["eps"])
    gates = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                                   vals["0.ffn.router.w"], precision=HI))
    w, idx = jax.lax.top_k(gates, d["top_k"])
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    def bank(name, a):
        return jax.vmap(lambda c: qgemm(a, c, m[f"0.ffn.qp_{name}"], bits))(
            vals[f"0.ffn.qp_{name}"])

    hid = jax.nn.silu(bank("wg", h)) * bank("wi", h)            # [E, T, F]
    y = jax.vmap(lambda hh, c: qgemm(hh, c, m["0.ffn.qp_wo"], bits))(
        hid, vals["0.ffn.qp_wo"])                                # [E, T, D]
    picked = y[idx, jnp.arange(x.shape[0])[:, None]]             # [T, k, D]
    return x + jnp.sum(picked * w[..., None].astype(x.dtype), axis=1)


def reference(key, cfg, dims: dict, abstract, t_pad: int) -> Reference:
    draw = weights.unit_fn(abstract, float_init)
    return Reference(
        layer,
        {"n_units": cfg.n_units, "n_heads": dims["n_heads"],
         "n_kv_heads": dims["n_kv_heads"], "hd": dims["hd"],
         "rope_fraction": dims["rope_fraction"],
         "rope_theta": dims["rope_theta"], "eps": dims["eps"],
         "top_k": dims["top_k"]},
        weights.linear_meta(abstract, mean_abs_in),
        lambda u: draw(key, u),
        weights.float_leaf(key, abstract, float_init, "embed", "table"),
        weights.float_leaf(key, abstract, float_init, "head", "w"),
        t_pad=t_pad)


def call_costs(dims: dict, call: dict, peak: dict) -> dict:
    """As ``dense.call_costs``, with the MLP's three projections run by
    every expert over its capacity of ceil(M k / E cf) rows."""
    d, f, e = dims["d_model"], dims["d_ff"], dims["n_experts"]
    i8, bw = peak["int8_ops"], peak["hbm_bytes_s"]
    attn = dense.linear_shapes(dims)[:4]

    def gemm_s(m):
        cap = math.ceil(m * dims["top_k"] / e * dims["capacity_factor"])
        shapes = [(m, k, n) for k, n in attn] + e * [(cap, d, f), (cap, d, f),
                                                      (cap, f, d)]
        return dims["n_layers"] * sum(
            counts.roofline_seconds(counts.gemm_ops(*s),
                                    counts.gemm_bytes(*s, dims["n_p"]),
                                    i8, bw) for s in shapes)

    out = dense.call_costs(dims, call, peak)
    rows = ([call["rows"]] * len(call["steps"]) if call["kind"] == "decode"
            else [call["chunk"]])
    out["gemm_s"] = sum(gemm_s(m) for m in rows)
    # model ops: a token passes top_k experts' MLP, not a d_ff MLP of one
    per_token = 2.0 * dims["n_layers"] * (dims["top_k"] - 1) * 3 * d * f
    out["model_ops"] += out["tokens"] * per_token + 2.0 * out["tokens"] * (
        dims["n_layers"] * d * e)
    return out
