"""Pallas TPU kernel: decode attention over an INT8 KV cache (PO2 scales).

Decode attention streams every live KV position through the chip once
per token.  Storing the cache as INT8 codes with power-of-two per-(batch, head) scales halves the
streamed bytes; the dequantization is a multiply by 2^e folded into the
score scale (the RAE shifter argument of §II-B, applied to serving).

Grid: ``(B, Hkv / hb, S / block_s)`` — batch and kv-head-group
parallel, the KV sequence dimension sequential with an online-softmax
VMEM scratch carry (m, l, acc), exactly the flash-decode structure.
Each step takes ``hb`` KV heads (``kv_heads_per_step``):

  * q tile  [hb, rows, hd]     VMEM (fp32; rows = chunk * G)
  * k tile  [hb, block_s, hd]  VMEM (int8)   <- the bandwidth win
  * v tile  [hb, block_s, hd]  VMEM (int8)
  * exps, length  SMEM scalars, prefetched (shift exponents per
    (b, h); valid length per b)
  * scratch m [hb, rows, 1], l [hb, rows, 1], acc [hb, rows, hd] fp32

A tile spans many pages (``block_s`` comes from shapes alone:
``repro.exec.kv_tile``), because a grid step has a fixed cost that a
page-sized tile cannot amortize.  Tiles wholly past a slot's length are
skipped: the K/V index map clamps to the slot's last live tile, so the
pipeline sees a repeated block and fetches nothing, and the body runs
under ``pl.when``.  Every query row must see at least one position
(``length >= chunk``), as every serving call does.  A one-row q tile
(decode with G = 1, as in MHA) computes q.k and p.v as f32
multiply-and-reduce on the VPU; more rows run f32 dots on the MXU at
``Precision.HIGHEST``.

The cache arrives as ``[B, S, Hkv, hd]`` and is laid out head-major
(``[B, Hkv, S, hd]``) before the launch, so each k/v tile is a
``(hb, block_s, hd)`` slab: the last two block dims are then legal
Mosaic tiles (``block_s`` a multiple of ``KV_SUBLANE`` or all of S,
``hd`` the whole head; ``legal_block_s`` snaps a request).

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
# K and V code bytes one grid step may take: enough KV heads per step to
# amortize the step's fixed cost, well inside Mosaic's scoped VMEM with
# double buffering and the f32 temporaries.
KV_STEP_BYTES = 1 << 20


def legal_block_s(seq_len: int, requested: int) -> int:
    """Largest divisor of ``seq_len`` <= ``requested`` that is a multiple
    of ``KV_SUBLANE``; all of ``seq_len`` when there is none."""
    for b in range(min(requested, seq_len) // KV_SUBLANE, 0, -1):
        if seq_len % (b * KV_SUBLANE) == 0:
            return b * KV_SUBLANE
    return seq_len


def kv_heads_per_step(n_kv_heads: int, block_s: int, hd: int) -> int:
    """KV heads per grid step: the largest divisor of ``n_kv_heads``
    whose K and V tiles fit ``KV_STEP_BYTES`` (at least one head)."""
    cap = max(1, KV_STEP_BYTES // (2 * block_s * hd))
    return max(d for d in range(1, min(cap, n_kv_heads) + 1)
               if n_kv_heads % d == 0)


def _kv_attn_kernel(kexp_ref, vexp_ref, len_ref, q_ref, k_ref, v_ref,
                    out_ref, m_ref, l_ref, acc_ref, *,
                    block_s: int, scale: float, chunk: int, group: int,
                    heads: int):
    b = pl.program_id(0)
    hg = pl.program_id(1)
    s = pl.program_id(2)
    length = len_ref[b]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s * block_s < length)
    def _tile():
        for j in range(heads):
            h = hg * heads + j
            q = q_ref[0, j]                       # [chunk * G, hd] fp32
            k = k_ref[0, j].astype(jnp.float32)   # [block_s, hd] codes
            v = v_ref[0, j].astype(jnp.float32)
            k_scale = jnp.exp2(kexp_ref[b, h].astype(jnp.float32))
            v_scale = jnp.exp2(vexp_ref[b, h].astype(jnp.float32))
            # The PO2 dequant folds into the softmax scale.  Causal over
            # the chunk: query row r is chunk token t = r // G, whose cache
            # position is len - chunk + t; it sees positions < that + 1.
            # chunk == 1 reduces to the decode mask (pos < len).
            m_prev = m_ref[j]                     # [chunk * G, 1]
            if q.shape[0] == 1:
                # One row: scores run down the sublanes, [block_s, 1].
                sc = jnp.sum(k * q, axis=1, keepdims=True)
                sc = sc * (scale * k_scale)
                pos = s * block_s + jax.lax.broadcasted_iota(
                    jnp.int32, sc.shape, 0)
                sc = jnp.where(pos < length, sc, NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0,
                                                    keepdims=True))
                p = jnp.exp(sc - m_new)
                l_new = jnp.sum(p, axis=0, keepdims=True)
                pv = jnp.sum(p * v, axis=0, keepdims=True)
            else:
                sc = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                sc = sc * (scale * k_scale)       # [chunk * G, block_s]
                pos = s * block_s + jax.lax.broadcasted_iota(
                    jnp.int32, sc.shape, 1)
                row_t = jax.lax.broadcasted_iota(jnp.int32, sc.shape,
                                                 0) // group
                sc = jnp.where(pos < length - chunk + 1 + row_t, sc,
                               NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1,
                                                    keepdims=True))
                p = jnp.exp(sc - m_new)
                l_new = jnp.sum(p, axis=1, keepdims=True)
                pv = jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * corr + l_new
            acc_ref[j] = acc_ref[j] * corr + pv * v_scale
            m_ref[j] = m_new

    @pl.when(s == pl.num_programs(2) - 1)
    def _done():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


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
    hb = kv_heads_per_step(Hkv, block_s, hd)
    scale = 1.0 / math.sqrt(hd)

    # [B, C, Hkv, G, hd] -> [B, Hkv, C*G, hd]: all of a kv-head's chunk
    # rows land in one q tile.
    qr = jnp.moveaxis(q.reshape(B, C, Hkv, G, hd).astype(jnp.float32),
                      1, 2).reshape(B, Hkv, CG, hd)
    k_heads = jnp.swapaxes(k_codes, 1, 2)   # [B, Hkv, S, hd]
    v_heads = jnp.swapaxes(v_codes, 1, 2)

    def q_map(b, hg, s, kexp, vexp, lens):
        return (b, hg, 0, 0)

    def kv_map(b, hg, s, kexp, vexp, lens):
        # Past the slot's last live tile, repeat it: nothing is fetched.
        last = jnp.maximum(lens[b] - 1, 0) // block_s
        return (b, hg, jnp.minimum(s, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                       # k_exp, v_exp, length
        grid=(B, Hkv // hb, S // block_s),
        in_specs=[
            pl.BlockSpec((1, hb, CG, hd), q_map),
            pl.BlockSpec((1, hb, block_s, hd), kv_map),
            pl.BlockSpec((1, hb, block_s, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hb, CG, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, CG, 1), jnp.float32),
            pltpu.VMEM((hb, CG, 1), jnp.float32),
            pltpu.VMEM((hb, CG, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kv_attn_kernel, block_s=block_s, scale=scale,
                          chunk=C, group=G, heads=hb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, CG, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(k_exp, v_exp, length, qr, k_heads, v_heads)
    out = jnp.moveaxis(out.reshape(B, Hkv, C, G, hd),
                       2, 1).reshape(B, C, Hq, hd)
    return out[:, 0] if squeeze else out
