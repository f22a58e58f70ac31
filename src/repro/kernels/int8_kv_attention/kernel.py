"""Pallas TPU kernel: decode attention over an INT8 KV cache (PO2 scales).

Decode attention is HBM-bandwidth-bound (every decode cell in §Roofline):
the whole KV cache streams through the chip once per token.  Storing the
cache as INT8 codes with power-of-two per-(batch, head) scales halves the
streamed bytes; the dequantization is a multiply by 2^e folded into the
score scale (the RAE shifter argument of §II-B, applied to serving).

Grid: ``(B, Hkv, S / block_s)`` — batch and kv-head parallel, the KV
sequence dimension sequential with an online-softmax VMEM scratch carry
(m, l, acc), exactly the flash-decode structure:

  * q tile  [G, hd]          VMEM (fp32)
  * k tile  [block_s, hd]    VMEM (int8)   <- the bandwidth win
  * v tile  [block_s, hd]    VMEM (int8)
  * exps    SMEM scalars (shift exponents per (b, h))
  * scratch m [G, 1], l [G, 1], acc [G, hd] fp32

The cache arrives as ``[B, S, Hkv, hd]`` and is laid out head-major
(``[B, Hkv, S, hd]``) before the launch, so each k/v tile is a
``(block_s, hd)`` slab of one head: the last two block dims are then
legal Mosaic tiles (``block_s`` a multiple of ``KV_SUBLANE`` or all of
S, ``hd`` the whole head; ``legal_block_s`` snaps a request).  Per-head
blocks of the seq-major layout would put a block of 1 on the Hkv axis,
which Mosaic refuses.

Validated against ``ref.int8_kv_attention_ref`` in interpret mode
(tests/test_kernels_kv.py) and within quantization tolerance of the fp32
attention reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Pallas's TPU block rule: the second-minor block dim (a k/v tile's
# ``block_s``) is a multiple of 8 or the whole axis.
KV_SUBLANE = 8


def legal_block_s(seq_len: int, requested: int) -> int:
    """Largest divisor of ``seq_len`` <= ``requested`` that is a multiple
    of ``KV_SUBLANE``; all of ``seq_len`` when there is none."""
    for b in range(min(requested, seq_len) // KV_SUBLANE, 0, -1):
        if seq_len % (b * KV_SUBLANE) == 0:
            return b * KV_SUBLANE
    return seq_len


def _kv_attn_kernel(kexp_ref, vexp_ref, len_ref, q_ref, k_ref, v_ref,
                    out_ref, m_ref, l_ref, acc_ref, *,
                    n_blocks: int, block_s: int, scale: float,
                    chunk: int, group: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                       # [chunk * G, hd] fp32
    k = k_ref[0, 0].astype(jnp.float32)   # [block_s, hd] int8 codes
    v = v_ref[0, 0].astype(jnp.float32)
    k_scale = jnp.exp2(kexp_ref[b, h].astype(jnp.float32))
    v_scale = jnp.exp2(vexp_ref[b, h].astype(jnp.float32))

    # scores over codes; the PO2 dequant folds into the softmax scale.
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    sc = sc * (scale * k_scale)           # [chunk * G, block_s]
    pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    # Causal over the chunk: query row r is chunk token t = r // G, whose
    # cache position is len - chunk + t; it sees positions < that + 1.
    # chunk == 1 reduces to the decode mask (pos < len).
    row_t = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) // group
    sc = jnp.where(pos < len_ref[b] - chunk + 1 + row_t, sc, NEG_INF)

    m_prev = m_ref[...]                   # [chunk * G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv * v_scale
    m_ref[...] = m_new

    @pl.when(s == n_blocks - 1)
    def _done():
        out_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret"))
def int8_kv_attention_kernel(
    q: jax.Array,        # [B, Hq, hd] or [B, C, Hq, hd] fp32
    k_codes: jax.Array,  # [B, S, Hkv, hd] int8
    v_codes: jax.Array,  # [B, S, Hkv, hd] int8
    k_exp: jax.Array,    # [B, Hkv] int32
    v_exp: jax.Array,    # [B, Hkv] int32
    length: jax.Array,   # [B] int32 valid cache length
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """3D q: one decode row.  4D q: a [chunk] of causal prefill rows whose
    last row sits at cache position ``length - 1`` (same flash-decode
    grid; the chunk rides the query-row axis of the q tile, so the MXU
    sees ``chunk * G`` score rows per (b, h) instead of ``G``)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C, Hq, hd = q.shape
    S, Hkv = k_codes.shape[1], k_codes.shape[2]
    G = Hq // Hkv
    CG = C * G
    assert S % block_s == 0, (S, block_s)
    assert block_s == S or block_s % KV_SUBLANE == 0, (S, block_s)
    n_blocks = S // block_s
    scale = 1.0 / math.sqrt(hd)

    # [B, C, Hkv, G, hd] -> [B, Hkv, C*G, hd]: all of a kv-head's chunk
    # rows land in one q tile.
    qr = jnp.moveaxis(q.reshape(B, C, Hkv, G, hd).astype(jnp.float32),
                      1, 2).reshape(B, Hkv, CG, hd)
    k_heads = jnp.swapaxes(k_codes, 1, 2)   # [B, Hkv, S, hd]
    v_heads = jnp.swapaxes(v_codes, 1, 2)
    grid = (B, Hkv, n_blocks)
    out = pl.pallas_call(
        functools.partial(_kv_attn_kernel, n_blocks=n_blocks,
                          block_s=block_s, scale=scale, chunk=C, group=G),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # k_exp
            pl.BlockSpec(memory_space=pltpu.SMEM),   # v_exp
            pl.BlockSpec(memory_space=pltpu.SMEM),   # length
            pl.BlockSpec((1, 1, CG, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CG, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, CG, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(k_exp, v_exp, length, qr, k_heads, v_heads)
    out = jnp.moveaxis(out.reshape(B, Hkv, C, G, hd),
                       2, 1).reshape(B, C, Hq, hd)
    return out[:, 0] if squeeze else out
