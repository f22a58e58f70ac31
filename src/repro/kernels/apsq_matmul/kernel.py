"""Pallas TPU kernel: W8A8 GEMM with INT8 additive-partial-sum banks.

TPU-native adaptation of the paper's Reconfigurable APSQ Engine (RAE):

  * the grid's K dimension IS the PSUM tiling — one grid step per PSUM tile
    ``T_pi`` (``n_p = K / block_k``, the paper's ``ceil(C_i / P_ci)``),
  * the RAE's four PSUM SRAM banks become a ``[gs, bm, bn]`` INT8 VMEM
    scratch — the running accumulator lives at 1 byte/element instead of the
    4 bytes/element an INT32 accumulator needs (the paper's beta: 4 -> 1),
  * quant/dequant are shift operations (power-of-two scales), matching the
    RAE's shifter modules: ``quantize = clip((v + 2^(e-1)) >> e)``,
    ``dequantize = code << e``,
  * the RAE's s0/s1/s2 mux encodings become compile-time specialization on
    the static ``gs`` — each group size compiles its own kernel, which is
    the TPU-idiomatic form of "reconfigurability".

Grid: ``(M/bm, N/bn, n_p)`` with the K dimension sequential ("arbitrary")
so the banks persist across PSUM tiles of one output tile.  Block specs put
x/w/out tiles in VMEM; the per-tile shift exponents sit in SMEM.

Three launch geometries share the Algorithm-1 body:

  * the generic grid above (``apsq_matmul_kernel``),
  * the m=1 decode fast path (``apsq_matmul_m1_kernel``) — grid ``(N/bn,)``
    with the whole K row resident in VMEM and the PSUM recurrence unrolled
    in-register (no bank scratch, no K grid steps: single-token decode is
    grid-overhead-bound, not compute-bound),
  * the fused MoE expert grid (``apsq_expert_matmul_kernel``) — grid
    ``(E, M/bm, N/bn, n_p)`` so ONE ``pallas_call`` serves every expert of
    a stacked ``DeployedQuantState`` bank, with each expert's exponent
    bank indexed by the leading grid coordinate.

Block sizes come from ``repro.kernels.autotune`` (per-shape-class cached
winners with a static heuristic fallback) unless the caller pins them.

Validated bit-exact against ``ref.apsq_matmul_ref`` in interpret mode
(tests/test_kernels.py sweeps shapes, gs, n_p and adversarial exponents).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MIN, INT8_MAX = -128, 127


def _rshift_round(v, e):
    """(v + 2^(e-1)) >> e with e >= 0 (e may be traced)."""
    e = jnp.asarray(e, jnp.int32)
    bias = jnp.where(e > 0, jnp.left_shift(1, jnp.maximum(e - 1, 0)), 0)
    return jnp.where(e > 0, jnp.right_shift(v + bias, e), v)


def _quantize(v, e):
    return jnp.clip(_rshift_round(v, e), INT8_MIN, INT8_MAX).astype(jnp.int8)


def _dequantize(code, e):
    return jnp.left_shift(code.astype(jnp.int32), jnp.asarray(e, jnp.int32))


def _read_exp(exp_ref, i, *, col0=None, block_n=None):
    """Shift exponent(s) for PSUM tile ``i`` (static int or program_id).

    1-D exps ([n_p] in SMEM): scalar per tile — per-tensor weight scales.
    2-D exps in VMEM: one exponent row per tile — the per-channel export
    layout (``psum_exps[:, N]``); the [1, bn] row broadcasts over the
    [bm, bn] accumulator in the shift helpers.  With the "blocked" layout
    the ref already holds this tile's [n_p, block_n] column slice; with
    the "full" layout (``col0`` given) the whole [n_p, N] table is
    resident and the column window is sliced dynamically.
    """
    if len(exp_ref.shape) == 2:
        if col0 is not None:
            return exp_ref[pl.dslice(i, 1), pl.dslice(col0, block_n)]
        return exp_ref[pl.dslice(i, 1), :]
    return exp_ref[i]


def _algorithm1_unrolled(prod, exp, *, n_p: int, gs: int):
    """Algorithm 1 over statically-unrolled PSUM tiles, fully in-register.

    ``prod(i)`` yields the INT32 partial-sum tile ``i``; ``exp(i)`` its
    shift exponent(s).  Mirrors ``ref.apsq_matmul_ref`` tile for tile
    (group starts fold the previous group's codes, tails are plain PSQ,
    the final tile requantizes once more) with Python control flow only —
    n_p and gs are static, so the whole recurrence unrolls.  Used by the
    m=1 fast path where tiles are column slices of one resident K row.
    """
    stored: list = [None] * n_p
    for i in range(0, n_p, gs):
        acc = prod(i)
        for j in range(max(0, i - gs), i):
            acc = acc + _dequantize(stored[j], exp(j))
        code = _quantize(acc, exp(i))
        stored[i] = code
        if i == n_p - 1:
            return _dequantize(code, exp(i))
        for j in range(i + 1, min(i + gs, n_p)):
            if j < n_p - 1:
                stored[j] = _quantize(prod(j), exp(j))
            else:  # final tile closes out mid-group
                acc = prod(j)
                for l in range(i, n_p - 1):
                    acc = acc + _dequantize(stored[l], exp(l))
                code = _quantize(acc, exp(j))
                return _dequantize(code, exp(j))
    raise AssertionError("unreachable")


def _apsq_kernel(exp_ref, x_ref, w_ref, out_ref, banks_ref, *, n_p: int,
                 gs: int, exp_layout: str = "blocked", block_n: int = 0):
    """One grid step = one PSUM tile T_pk of one (i, j) output tile."""
    k = pl.program_id(2)
    col0 = pl.program_id(1) * block_n if exp_layout == "full" else None
    exp = functools.partial(_read_exp, exp_ref, col0=col0, block_n=block_n)
    prod = jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # int8 x int8 -> int32 on the MXU

    if n_p == 1:
        # Single PSUM tile: output quantization only (Algorithm 1 line 2).
        out_ref[...] = _dequantize(_quantize(prod, exp(0)), exp(0))
        return

    last = n_p - 1
    last_start = (last // gs) * gs

    @pl.when(k == 0)
    def _first():  # AP*_0 = Q_0(T_p0)
        banks_ref[0] = _quantize(prod, exp(0))

    @pl.when((k > 0) & (k % gs == 0) & (k < last))
    def _group_start():  # APSQ: fold the previous group's banks back in
        acc = prod
        for j in range(gs):  # bank j holds tile (k - gs + j)
            acc = acc + _dequantize(banks_ref[j], exp(k - gs + j))
        banks_ref[0] = _quantize(acc, exp(k))

    @pl.when((k > 0) & (k % gs != 0) & (k < last))
    def _tail():  # plain PSQ on a tail tile
        banks_ref[pl.ds(k % gs, 1)] = _quantize(prod, exp(k))[None]

    @pl.when(k == last)
    def _final():
        # Statically known: which banks are live and their tile indices.
        acc = prod
        if last % gs == 0:  # final tile is itself a group start -> APSQ
            if last > 0:
                for j in range(gs):
                    acc = acc + _dequantize(banks_ref[j], exp(last - gs + j))
        else:  # mid-group: fold the stored tiles since last_start
            for l in range(last_start, last):
                acc = acc + _dequantize(banks_ref[l - last_start], exp(l))
        out_ref[...] = _dequantize(_quantize(acc, exp(last)), exp(last))


def _baseline_kernel(x_ref, w_ref, out_ref, acc_ref, *, n_p: int):
    """INT32-accumulator W8A8 GEMM — the high-precision-PSUM baseline.

    Identical grid/blocking, but the running PSUM is a [bm, bn] INT32 VMEM
    scratch: 4 bytes/element, the paper's beta = 4 working set.
    """
    k = pl.program_id(2)
    prod = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prod

    @pl.when(k > 0)
    def _acc():
        acc_ref[...] = acc_ref[...] + prod

    @pl.when(k == n_p - 1)
    def _out():
        out_ref[...] = acc_ref[...] if n_p > 1 else prod


def _compiler_params(n_dims: int):
    """dimension_semantics: M/N parallel, K sequential (banks carry state)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_dims - 1) + ("arbitrary",))


def _parallel_params(n_dims: int):
    """All-parallel semantics (no cross-step state — the m=1 fast path)."""
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * n_dims)


@functools.partial(
    jax.jit,
    static_argnames=("gs", "block_m", "block_n", "n_p", "exp_layout",
                     "interpret"),
)
def apsq_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    exps: jax.Array,
    *,
    n_p: int,
    gs: int,
    block_m: int = 128,
    block_n: int = 128,
    exp_layout: str = "blocked",
    interpret: bool = False,
) -> jax.Array:
    """[M, K] int8 @ [K, N] int8 -> [M, N] int32 (product-scale units).

    ``M % block_m == 0``, ``N % block_n == 0``, ``K % n_p == 0`` — the ops.py
    wrapper pads.  ``exps`` is int32, exponents >= 0: [n_p] (per-tensor
    weight scales; SMEM scalars) or [n_p, N] (per-channel export layout).
    ``exp_layout`` picks how 2-D exponents reach VMEM: "blocked" streams a
    [n_p, block_n] column slice per output tile, "full" keeps the whole
    [n_p, N] table resident and slices dynamically (an autotunable axis).
    """
    m, kdim = x_codes.shape
    n = w_codes.shape[1]
    assert kdim % n_p == 0 and m % block_m == 0 and n % block_n == 0
    if exps.ndim == 2:
        assert exps.shape == (n_p, n), (exps.shape, n_p, n)
        if exp_layout == "full":
            exp_spec = pl.BlockSpec((n_p, n), lambda i, j, k: (0, 0))
        else:
            exp_spec = pl.BlockSpec((n_p, block_n), lambda i, j, k: (0, j))
    else:
        exp_layout = "blocked"  # layout only matters for 2-D exps
        exp_spec = pl.BlockSpec(memory_space=pltpu.SMEM)  # [n_p] scalars
    block_k = kdim // n_p

    grid = (m // block_m, n // block_n, n_p)
    return pl.pallas_call(
        functools.partial(_apsq_kernel, n_p=n_p, gs=gs,
                          exp_layout=exp_layout, block_n=block_n),
        grid=grid,
        in_specs=[
            exp_spec,
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((gs, block_m, block_n), jnp.int8)],
        compiler_params=_compiler_params(3),
        interpret=interpret,
    )(exps, x_codes, w_codes)


# ---------------------------------------------------------------------------
# m=1 decode fast path
# ---------------------------------------------------------------------------

def _apsq_m1_kernel(exp_ref, x_ref, w_ref, out_ref, *, n_p: int, gs: int,
                    block_k: int):
    """Single-token decode: one grid step per N tile, K unrolled in-register.

    ``x_ref`` holds the whole [1, K] code row, ``w_ref`` this tile's
    [K, block_n] column slab; PSUM tile ``i`` is a static column slice, so
    the Algorithm-1 recurrence runs fully unrolled with no bank scratch
    and no K grid steps — the decode shape is launch-overhead-bound, and
    this removes the n_p-step grid walk the generic kernel pays.
    """
    def prod(i):
        xs = x_ref[:, i * block_k:(i + 1) * block_k]
        ws = w_ref[i * block_k:(i + 1) * block_k, :]
        return jax.lax.dot_general(
            xs, ws, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    exp = functools.partial(_read_exp, exp_ref)
    out_ref[...] = _algorithm1_unrolled(prod, exp, n_p=n_p, gs=gs)


@functools.partial(
    jax.jit, static_argnames=("gs", "block_n", "n_p", "interpret"))
def apsq_matmul_m1_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    exps: jax.Array,
    *,
    n_p: int,
    gs: int,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """[1, K] int8 @ [K, N] int8 -> [1, N] int32 — the decode fast path.

    Same Algorithm-1 semantics as ``apsq_matmul_kernel`` (bit-exact), but
    grid ``(N/bn,)`` with the K reduction inlined per tile.  ``K % n_p``
    and ``N % block_n`` must be 0 (ops.py pads).
    """
    m, kdim = x_codes.shape
    n = w_codes.shape[1]
    assert m == 1 and kdim % n_p == 0 and n % block_n == 0
    if exps.ndim == 2:
        assert exps.shape == (n_p, n), (exps.shape, n_p, n)
        exp_spec = pl.BlockSpec((n_p, block_n), lambda j: (0, j))
    else:
        exp_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    block_k = kdim // n_p

    return pl.pallas_call(
        functools.partial(_apsq_m1_kernel, n_p=n_p, gs=gs, block_k=block_k),
        grid=(n // block_n,),
        in_specs=[
            exp_spec,
            pl.BlockSpec((1, kdim), lambda j: (0, 0)),
            pl.BlockSpec((kdim, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        compiler_params=_parallel_params(1),
        interpret=interpret,
    )(exps, x_codes, w_codes)


# ---------------------------------------------------------------------------
# Fused MoE expert grid
# ---------------------------------------------------------------------------

def _apsq_expert_kernel(exp_ref, x_ref, w_ref, out_ref, banks_ref, *,
                        n_p: int, gs: int):
    """One grid step = one PSUM tile of one (e, i, j) expert output tile.

    Identical Algorithm-1 body to ``_apsq_kernel``; the refs carry a
    leading singleton expert dim selected by grid coordinate 0, and the
    exponent read indexes that expert's bank.
    """
    k = pl.program_id(3)

    if len(exp_ref.shape) == 3:  # [1, n_p, block_n] — this expert's bank
        exp = lambda i: exp_ref[0, pl.dslice(i, 1), :]
    else:  # [E, n_p] whole table in SMEM
        e = pl.program_id(0)
        exp = lambda i: exp_ref[e, i]
    prod = jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    if n_p == 1:
        out_ref[0] = _dequantize(_quantize(prod, exp(0)), exp(0))
        return

    last = n_p - 1
    last_start = (last // gs) * gs

    @pl.when(k == 0)
    def _first():
        banks_ref[0] = _quantize(prod, exp(0))

    @pl.when((k > 0) & (k % gs == 0) & (k < last))
    def _group_start():
        acc = prod
        for j in range(gs):
            acc = acc + _dequantize(banks_ref[j], exp(k - gs + j))
        banks_ref[0] = _quantize(acc, exp(k))

    @pl.when((k > 0) & (k % gs != 0) & (k < last))
    def _tail():
        banks_ref[pl.ds(k % gs, 1)] = _quantize(prod, exp(k))[None]

    @pl.when(k == last)
    def _final():
        acc = prod
        if last % gs == 0:
            if last > 0:
                for j in range(gs):
                    acc = acc + _dequantize(banks_ref[j], exp(last - gs + j))
        else:
            for l in range(last_start, last):
                acc = acc + _dequantize(banks_ref[l - last_start], exp(l))
        out_ref[0] = _dequantize(_quantize(acc, exp(last)), exp(last))


def _baseline_expert_kernel(x_ref, w_ref, out_ref, acc_ref, *, n_p: int):
    """INT32-accumulator W8A8 expert GEMM on the fused (E, i, j, k) grid."""
    k = pl.program_id(3)
    prod = jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prod

    @pl.when(k > 0)
    def _acc():
        acc_ref[...] = acc_ref[...] + prod

    @pl.when(k == n_p - 1)
    def _out():
        out_ref[0] = acc_ref[...] if n_p > 1 else prod


@functools.partial(
    jax.jit,
    static_argnames=("gs", "block_m", "block_n", "n_p", "interpret"),
)
def apsq_expert_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    exps: jax.Array,
    *,
    n_p: int,
    gs: int,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """[E, M, K] int8 @ [E, K, N] int8 -> [E, M, N] int32, one launch.

    The expert axis is grid dimension 0 — every expert of a stacked MoE
    ``DeployedQuantState`` bank is served by this single ``pallas_call``,
    with per-expert exponent banks ([E, n_p] in SMEM or [E, n_p, N]
    streamed per column tile) selected by the grid coordinate.  Dims
    follow the generic kernel's contract per expert (ops.py pads).
    """
    n_e, m, kdim = x_codes.shape
    n = w_codes.shape[2]
    assert kdim % n_p == 0 and m % block_m == 0 and n % block_n == 0
    if exps.ndim == 3:
        assert exps.shape == (n_e, n_p, n), (exps.shape, n_e, n_p, n)
        exp_spec = pl.BlockSpec((1, n_p, block_n),
                                lambda e, i, j, k: (e, 0, j))
    else:
        assert exps.shape == (n_e, n_p), (exps.shape, n_e, n_p)
        exp_spec = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole [E, n_p]
    block_k = kdim // n_p

    grid = (n_e, m // block_m, n // block_n, n_p)
    return pl.pallas_call(
        functools.partial(_apsq_expert_kernel, n_p=n_p, gs=gs),
        grid=grid,
        in_specs=[
            exp_spec,
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_e, m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((gs, block_m, block_n), jnp.int8)],
        compiler_params=_compiler_params(4),
        interpret=interpret,
    )(exps, x_codes, w_codes)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "n_p", "interpret"))
def baseline_expert_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    n_p: int,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """INT32-accumulator W8A8 expert GEMM — fused (E, i, j, k) grid."""
    n_e, m, kdim = x_codes.shape
    n = w_codes.shape[2]
    assert kdim % n_p == 0 and m % block_m == 0 and n % block_n == 0
    block_k = kdim // n_p

    grid = (n_e, m // block_m, n // block_n, n_p)
    return pl.pallas_call(
        functools.partial(_baseline_expert_kernel, n_p=n_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_e, m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=_compiler_params(4),
        interpret=interpret,
    )(x_codes, w_codes)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "n_p", "interpret")
)
def baseline_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    n_p: int,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """INT32-accumulator W8A8 GEMM with the same grid/blocking as APSQ."""
    m, kdim = x_codes.shape
    n = w_codes.shape[1]
    assert kdim % n_p == 0 and m % block_m == 0 and n % block_n == 0
    block_k = kdim // n_p

    grid = (m // block_m, n // block_n, n_p)
    return pl.pallas_call(
        functools.partial(_baseline_kernel, n_p=n_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=_compiler_params(3),
        interpret=interpret,
    )(x_codes, w_codes)


def accumulator_vmem_bytes(block_m: int, block_n: int, gs: int) -> dict:
    """Accumulator working-set per output tile: APSQ banks vs INT32 baseline.

    This is the co-design win on TPU: beta 4 -> gs/4 of the baseline bytes
    (gs=1: 4x smaller; gs=4: parity in VMEM but still 4x fewer bytes per
    HBM spill in split-K schedules, since only one bank is in flight).
    """
    return {
        "apsq_banks": gs * block_m * block_n,          # gs INT8 banks
        "baseline_int32": 4 * block_m * block_n,        # one INT32 accum
    }
