"""Pure-jnp oracle for INT8-KV decode attention (PO2 scales).

The paper stores PSUMs as INT8 codes with power-of-two scales so that
dequantization is a shift (§II-B).  Applied to the *decode* path, the same
trick halves KV-cache bytes — and the decode roofline is pure HBM
bandwidth (§Roofline: every decode cell is memory-bound), so bytes are
latency.  Codes: int8; scales: 2^e per (batch, kv-head), exponents int32.

    out[b, h*G+g] = softmax_s( q . (k_codes[b,s,h] * 2^ke[b,h]) / sqrt(d) )
                    . (v_codes[b,s,h] * 2^ve[b,h])
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def quantize_kv_po2(x: jax.Array):
    """[B, S, H, hd] float -> (int8 codes, int32 exponents [B, H]).

    Scale = 2^ceil(log2(amax/127)): the smallest power of two whose
    127-code range covers the tensor (per batch x head)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 3))
    exp = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / 127.0)).astype(
        jnp.int32)
    scale = jnp.exp2(exp.astype(jnp.float32))[:, None, :, None]
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, exp


def dequantize_kv_po2(codes: jax.Array, exp: jax.Array,
                      dtype=jnp.float32) -> jax.Array:
    scale = jnp.exp2(exp.astype(jnp.float32))[:, None, :, None]
    return codes.astype(jnp.float32) * scale


def int8_kv_attention_ref(
    q: jax.Array,           # [B, Hq, hd] or [B, C, Hq, hd] float
    k_codes: jax.Array,     # [B, S, Hkv, hd] int8
    v_codes: jax.Array,     # [B, S, Hkv, hd] int8
    k_exp: jax.Array,       # [B, Hkv] int32
    v_exp: jax.Array,       # [B, Hkv] int32
    length: jax.Array | int,  # valid cache length (scalar or [B])
) -> jax.Array:
    """Oracle attention over the INT8 cache.

    Decode form (3D q): one query row per batch, attending to the first
    ``length`` cache positions; returns [B, Hq, hd].  Prefill-chunk form
    (4D q): C causal query rows whose LAST row sits at cache position
    ``length - 1`` — row ``t`` sees positions ``< length - C + 1 + t`` —
    returns [B, C, Hq, hd].  C = 1 reduces exactly to the decode form.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C, Hq, hd = q.shape
    S, Hkv = k_codes.shape[1], k_codes.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    k = dequantize_kv_po2(k_codes, k_exp)
    v = dequantize_kv_po2(v_codes, v_exp)
    qf = q.reshape(B, C, Hkv, G, hd).astype(jnp.float32)
    # HIGHEST: full-f32 dots, as the kernel computes them on the chip.
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bchgd,bshd->bchgs", qf, k, precision=hi) * scale
    limit = (jnp.reshape(jnp.asarray(length), (-1, 1)) - C + 1
             + jnp.arange(C)[None])                 # [B, C]
    valid = jnp.arange(S)[None, None] < limit[..., None]
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bchgs,bshd->bchgd", p, v, precision=hi)
    out = out.reshape(B, C, Hq, hd).astype(q.dtype)
    return out[:, 0] if squeeze else out


def fp_attention_ref(q, k, v, length):
    """Full-precision reference (tolerance anchor for the INT8 path)."""
    B, S, Hkv, hd = k.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None] < jnp.reshape(jnp.asarray(length), (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)
