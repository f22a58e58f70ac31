"""Jit'd public wrappers for the INT8-KV decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import int8_kv_attention_kernel, legal_block_s
from .ref import quantize_kv_po2


def int8_kv_attention(
    q: jax.Array,        # [B, Hq, hd] decode or [B, C, Hq, hd] chunk
    k_codes: jax.Array,  # [B, S, Hkv, hd] int8
    v_codes: jax.Array,
    k_exp: jax.Array,    # [B, Hkv] int32
    v_exp: jax.Array,
    length: jax.Array | int,
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Attention over an INT8 cache, matching q's rank.

    3D q is the decode form (one row per batch); 4D q is a prefill chunk
    of C causal rows ending at cache position ``length - 1``.  Returns
    [B, Hq, hd] / [B, C, Hq, hd] in q's dtype.  ``block_s`` must divide
    S; a size the chip cannot tile is snapped to ``legal_block_s``.
    """
    B, S = k_codes.shape[:2]
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} not divisible by block_s={block_s}")
    block_s = legal_block_s(S, block_s)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    out = int8_kv_attention_kernel(
        q, k_codes, v_codes, k_exp.astype(jnp.int32),
        v_exp.astype(jnp.int32), length, block_s=block_s,
        interpret=interpret)
    return out.astype(q.dtype)


def int8_kv_attention_f32(q, k, v, length, *, block_s: int = 512,
                          interpret: bool = False):
    """Float entry: quantize the cache (PO2) then run the kernel."""
    k_codes, k_exp = quantize_kv_po2(k)
    v_codes, v_exp = quantize_kv_po2(v)
    return int8_kv_attention(q, k_codes, v_codes, k_exp, v_exp, length,
                             block_s=block_s, interpret=interpret)


def cache_bytes(B: int, S: int, Hkv: int, hd: int) -> dict:
    """The bandwidth story: INT8 cache vs bf16 per decode step."""
    return {
        "int8": B * S * Hkv * hd * 2 * 1 + B * Hkv * 2 * 4,  # + exps
        "bf16": B * S * Hkv * hd * 2 * 2,
    }
