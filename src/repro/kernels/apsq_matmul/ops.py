"""Jit'd public wrappers around the APSQ Pallas kernels.

Handles padding to block multiples, operand quantization from float, and
rescaling of the integer result back to float.  The kernels compile for the
TPU (Mosaic); ``interpret=True`` runs them in the Pallas interpreter, which
is how the CPU tests exercise them.

Block sizes: every entry point takes ``block_m``/``block_n`` (and, where
2-D exponents are in play, ``exp_layout``).  Left as ``None`` they resolve
through ``repro.kernels.autotune.get_block_config`` — the per-shape-class
cache of tuned winners with a static heuristic fallback — so callers get
shape-appropriate launch geometry (m=1 decode fast path, large prefill
tiles, fused expert blocks) without naming blocks anywhere.  Explicit
values are respected, clamped to the padded operand dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import autotune
from . import ref
from .kernel import (
    apsq_expert_matmul_kernel,
    apsq_matmul_kernel,
    apsq_matmul_m1_kernel,
    baseline_expert_matmul_kernel,
    baseline_matmul_kernel,
)


# Mosaic tiles the minor dimension of a block in 128-lane vectors.
LANE = 128


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad_psum_tiles(x_codes: jax.Array, w_codes: jax.Array, n_p: int):
    """Zero-pad K so every PSUM tile's K slice is a whole number of lanes.

    ``x_codes`` [..., M, K], ``w_codes`` [..., K, N].  K first gets the
    ragged remainder group of ``ref.pad_ragged_k`` (tile width
    ``ceil(K / n_p)``); then each tile's slice is padded on its own to a
    multiple of ``LANE``, which the generic and expert grids need for a
    legal ``block_k`` on the chip.  Zero codes add nothing, and every K
    index stays in its own tile, so the result is bit-identical to the
    unpadded Algorithm 1.  (Padding K as a whole would move tile
    boundaries.)  n_p == 1 needs no lane padding: its block is all of K.
    """
    k = x_codes.shape[-1]
    kt = -(-k // n_p)
    ktp = kt if n_p == 1 else _round_up(kt, LANE)
    if ktp * n_p == k:
        return x_codes, w_codes
    lead_x, lead_w = x_codes.shape[:-1], w_codes.shape[:-2]
    n = w_codes.shape[-1]
    pad_x = [(0, 0)] * (x_codes.ndim - 1)
    pad_w = [(0, 0)] * (w_codes.ndim - 2)
    x = jnp.pad(x_codes, pad_x + [(0, n_p * kt - k)])
    w = jnp.pad(w_codes, pad_w + [(0, n_p * kt - k), (0, 0)])
    x = jnp.pad(x.reshape(*lead_x, n_p, kt),
                pad_x + [(0, 0), (0, ktp - kt)])
    w = jnp.pad(w.reshape(*lead_w, n_p, kt, n),
                pad_w + [(0, 0), (0, ktp - kt), (0, 0)])
    return (x.reshape(*lead_x, n_p * ktp),
            w.reshape(*lead_w, n_p * ktp, n))


def _resolve_blocks(m, k, n, *, n_p, gs, block_m, block_n, exp_layout=None,
                    expert=False):
    """Fill in unset block params from the autotune table, then clamp to
    the padded operand dims (a block never exceeds what one tile covers)."""
    cfg = None
    if block_m is None or block_n is None or exp_layout is None:
        cfg = autotune.get_block_config(m, k, n, n_p=n_p, gs=gs,
                                        expert=expert)
    bm = cfg.block_m if block_m is None else block_m
    bn = cfg.block_n if block_n is None else block_n
    layout = (cfg.exp_layout if cfg is not None else "blocked") \
        if exp_layout is None else exp_layout
    if bm != 1:
        bm = max(1, min(bm, _round_up(m, 8)))
    if n < 128:  # unit-test shapes: one lane tile, no column padding
        bn = n
    else:
        bn = max(128, min(bn, _round_up(n, 128)))
    return bm, bn, layout


def apsq_matmul_int8(
    x_codes: jax.Array,
    w_codes: jax.Array,
    exps: jax.Array,
    *,
    gs: int,
    block_m: int | None = None,
    block_n: int | None = None,
    exp_layout: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """INT8 GEMM with Algorithm-1 PSUM handling; returns INT32 [M, N].

    ``n_p`` is taken from ``exps.shape[0]``.  Ragged ``K % n_p != 0`` is
    handled by zero-padding K into a remainder PSUM group (zero codes
    contribute nothing to the final tile's partial sum).  ``exps`` is
    [n_p] (per-tensor) or [n_p, N] (per-channel weight scales).

    M == 1 with an unpinned ``block_m`` takes the decode fast path
    (``apsq_matmul_m1_kernel``: one grid row over N, the whole K
    reduction unrolled in-register) — bit-identical to the generic grid.
    """
    m, k = x_codes.shape
    n = w_codes.shape[1]
    n_p = int(exps.shape[0])
    bm, bn, layout = _resolve_blocks(m, k, n, n_p=n_p, gs=gs,
                                     block_m=block_m, block_n=block_n,
                                     exp_layout=exp_layout)
    exps = exps.astype(jnp.int32)
    if exps.ndim == 2:  # pad the column axis alongside w (exponent 0 is id)
        exps = _pad_to(exps, 1, bn)
    if m == 1 and bm == 1:
        # The fast path keeps all of K resident: only the ragged group.
        x_codes, w_codes = ref.pad_ragged_k(x_codes, w_codes, n_p)
        wp = _pad_to(w_codes, 1, bn)
        out = apsq_matmul_m1_kernel(
            x_codes, wp, exps, n_p=n_p, gs=int(gs), block_n=bn,
            interpret=interpret)
        return out[:, :n]
    bm = max(bm, 8)  # the generic grid pads rows to sublane multiples
    x_codes, w_codes = pad_psum_tiles(x_codes, w_codes, n_p)
    xp = _pad_to(x_codes, bm, 1)
    wp = _pad_to(w_codes, 1, bn)
    out = apsq_matmul_kernel(
        xp, wp, exps,
        n_p=n_p, gs=int(gs), block_m=bm, block_n=bn, exp_layout=layout,
        interpret=interpret,
    )
    return out[:m, :n]


def apsq_expert_matmul_int8(
    x_codes: jax.Array,
    w_codes: jax.Array,
    exps: jax.Array,
    *,
    gs: int,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused expert-bank GEMM: [E, M, K] @ [E, K, N] -> [E, M, N] INT32.

    ONE ``pallas_call`` serves all E experts (the expert axis is grid
    dimension 0).  ``exps`` carries per-expert exponent banks: [E, n_p]
    (per-tensor) or [E, n_p, N] (per-channel).  Ragged ``K % n_p`` gets
    the same zero-contribution remainder group as the single-expert path.
    """
    n_e, m, k = x_codes.shape
    n = w_codes.shape[2]
    n_p = int(exps.shape[1])
    bm, bn, _ = _resolve_blocks(m, k, n, n_p=n_p, gs=gs, block_m=block_m,
                                block_n=block_n, exp_layout="blocked",
                                expert=True)
    bm = max(bm, min(8, _round_up(m, 8)))  # expert grid has no m=1 path
    x_codes, w_codes = pad_psum_tiles(x_codes, w_codes, n_p)
    pad_m = (-m) % bm
    pad_n = (-n) % bn
    if pad_m:
        x_codes = jnp.pad(x_codes, ((0, 0), (0, pad_m), (0, 0)))
    if pad_n:
        w_codes = jnp.pad(w_codes, ((0, 0), (0, 0), (0, pad_n)))
    exps = exps.astype(jnp.int32)
    if exps.ndim == 3 and pad_n:
        exps = jnp.pad(exps, ((0, 0), (0, 0), (0, pad_n)))
    out = apsq_expert_matmul_kernel(
        x_codes, w_codes, exps,
        n_p=n_p, gs=int(gs), block_m=bm, block_n=bn, interpret=interpret,
    )
    return out[:, :m, :n]


def baseline_expert_matmul_int8(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    n_p: int = 1,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused INT32-accumulator W8A8 expert GEMM; returns INT32 [E, M, N]."""
    n_e, m, k = x_codes.shape
    n = w_codes.shape[2]
    bm, bn, _ = _resolve_blocks(m, k, n, n_p=n_p, gs=1, block_m=block_m,
                                block_n=block_n, exp_layout="blocked",
                                expert=True)
    bm = max(bm, min(8, _round_up(m, 8)))
    x_codes, w_codes = pad_psum_tiles(x_codes, w_codes, n_p)
    pad_m, pad_n = (-m) % bm, (-n) % bn
    if pad_m:
        x_codes = jnp.pad(x_codes, ((0, 0), (0, pad_m), (0, 0)))
    if pad_n:
        w_codes = jnp.pad(w_codes, ((0, 0), (0, 0), (0, pad_n)))
    out = baseline_expert_matmul_kernel(
        x_codes, w_codes, n_p=n_p, block_m=bm, block_n=bn,
        interpret=interpret,
    )
    return out[:, :m, :n]


def baseline_matmul_int8(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    n_p: int,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """INT32-accumulator W8A8 GEMM baseline; returns INT32 [M, N]."""
    m, k = x_codes.shape
    n = w_codes.shape[1]
    bm, bn, _ = _resolve_blocks(m, k, n, n_p=n_p, gs=1, block_m=block_m,
                                block_n=block_n, exp_layout="blocked")
    bm = max(bm, min(8, _round_up(m, 8)))  # no m=1 kernel for the baseline
    x_codes, w_codes = pad_psum_tiles(x_codes, w_codes, n_p)
    xp = _pad_to(x_codes, bm, 1)
    wp = _pad_to(w_codes, 1, bn)
    out = baseline_matmul_kernel(
        xp, wp, n_p=n_p, block_m=bm, block_n=bn, interpret=interpret,
    )
    return out[:m, :n]


def quantize_operands(
    x: jax.Array, w: jax.Array, *, ax: jax.Array | float, aw: jax.Array | float
):
    """Float activations/weights -> INT8 codes with scales ax (per-tensor)
    and aw (per-tensor or per-column [N])."""
    xq = jnp.clip(jnp.round(x / ax), -128, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / aw), -128, 127).astype(jnp.int8)
    return xq, wq


def apsq_matmul_f32(
    x: jax.Array,
    w: jax.Array,
    exps: jax.Array,
    *,
    gs: int,
    ax: jax.Array | float,
    aw: jax.Array | float,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Deployment-path float entry: quantize -> integer kernel -> rescale.

    Output scale is product-scale ``ax * aw`` (aw broadcasts per-column).
    """
    xq, wq = quantize_operands(x, w, ax=ax, aw=aw)
    y = apsq_matmul_int8(
        xq, wq, exps, gs=gs, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )
    return y.astype(jnp.float32) * jnp.asarray(ax, jnp.float32) * jnp.asarray(
        aw, jnp.float32
    )


def calibrate_exps(
    x_codes: jax.Array, w_codes: jax.Array, *, n_p: int, gs: int
) -> jax.Array:
    """Exponent calibration from a sample batch (see ref.choose_exps)."""
    return ref.choose_exps(x_codes, w_codes, n_p=n_p, gs=gs)
