"""Execution backends for deployed integer GEMMs.

The deployed model (``DeployedQuantState`` params, see ``repro.quant.export``)
describes *what* to compute — INT8 codes, PO2 shift exponents, Algorithm-1
PSUM handling — but not *how*.  This module owns the "how": a small registry
of backends behind one entry point, ``execute_gemm``:

  * ``oracle`` — the pure-jnp integer semantics
    (``kernels/apsq_matmul/ref``).  Runs anywhere, shape-polymorphic,
    differentiable-adjacent; the reference all other backends must match
    bit-for-bit.
  * ``pallas`` — the real ``kernels/apsq_matmul`` Pallas TPU kernel
    (INT8 PSUM banks in VMEM), compiled for the chip: the hardware
    datapath the paper's energy claims (§V) ride on.  It never falls
    back: off the chip it fails.  ``PallasBackend(interpret=True)`` runs
    the same kernels in the Pallas interpreter, which is how the CPU
    tests exercise them.
  * ``auto``   — ``pallas`` when the default JAX backend is TPU, else
    ``oracle``.  A convenience for CPU tests and examples; the chip path
    names ``pallas`` explicitly so that a missing chip cannot pass
    unnoticed.

Every projection GEMM in the model zoo dispatches here when its params are
deployed (``models.common.dense`` -> ``core.deployed_dense`` ->
``execute_gemm``), including MoE expert banks and the tied-embedding head,
so QAT fake-quant, the oracle, and the kernel are provably one semantics
on a single code path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import DeployedQuantState, QuantConfig, qrange


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class ExecBackend:
    """How the integer op families on exported/quantized data are computed.

    Two op families, one registry:

    * ``int_gemm`` consumes INT8 activation codes [M, K], a deployed
      layer's weight codes [K, N] and PSUM shift exponents ([n_p] or
      [n_p, N]; None for plain W8A8) and returns the INT32 result in
      product-scale units.
    * ``kv_attention`` consumes a query (float), an INT8 KV cache
      ([B, S, Hkv, hd] codes with per-(batch, head) PO2 exponents) and
      per-batch valid lengths, and returns attention output — the serving
      engine's paged-cache read path.  A 3D query [B, Hq, hd] is one
      decode row; a 4D query [B, C, Hq, hd] is a causal prefill chunk
      whose last row sits at cache position ``length - 1``.
    """

    name = "base"

    def int_gemm(self, x_codes: jax.Array, w_codes: jax.Array,
                 psum_exps: jax.Array | None, *, gs: int) -> jax.Array:
        raise NotImplementedError

    def int_expert_gemm(self, x_codes: jax.Array, w_codes: jax.Array,
                        psum_exps: jax.Array | None, *,
                        gs: int) -> jax.Array:
        """Stacked expert-bank GEMM: [E, M, K] @ [E, K, N] -> [E, M, N].

        Default semantics: E independent ``int_gemm`` calls (the
        reference unrolled form).  Backends that can fuse the expert
        axis into one launch override this — the Pallas backend serves
        all experts from a single ``pallas_call`` grid.
        """
        n_exp = int(x_codes.shape[0])
        return jnp.stack([
            self.int_gemm(
                x_codes[e], w_codes[e],
                None if psum_exps is None else psum_exps[e], gs=gs)
            for e in range(n_exp)])

    def kv_attention(self, q: jax.Array, k_codes: jax.Array,
                     v_codes: jax.Array, k_exp: jax.Array,
                     v_exp: jax.Array, length: jax.Array) -> jax.Array:
        raise NotImplementedError

    def kv_tile(self, seq_len: int, head_dim: int, rows: int) -> int:
        """KV positions per grid step of the attention kernel over
        ``seq_len`` cache positions with ``rows`` query rows per KV head
        (``kv_tile``)."""
        return kv_tile(seq_len, head_dim, rows)

    def resolve(self) -> "ExecBackend":
        """The concrete backend that will execute (identity for leaves)."""
        return self

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class OracleBackend(ExecBackend):
    """Pure-jnp semantics (``apsq_matmul.ref`` / ``int8_kv_attention.ref``)."""

    name = "oracle"

    def int_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        from repro.kernels.apsq_matmul import ref  # lazy: keep import light
        if psum_exps is None:
            return ref.baseline_matmul_ref(x_codes, w_codes)
        n_p = int(psum_exps.shape[0])
        return ref.apsq_matmul_ref(x_codes, w_codes, psum_exps,
                                   n_p=n_p, gs=gs)

    def kv_attention(self, q, k_codes, v_codes, k_exp, v_exp, length):
        from repro.kernels.int8_kv_attention import int8_kv_attention_ref
        return int8_kv_attention_ref(q, k_codes, v_codes, k_exp, v_exp,
                                     length)


class PallasBackend(ExecBackend):
    """The real Pallas kernels, compiled for the TPU.

    ``interpret=True`` runs them in the Pallas interpreter instead (the
    CPU tests); the default never does, so off the chip it fails.

    Launch geometry comes from ``repro.kernels.autotune``: every GEMM
    resolves (block_m, block_n, exponent layout) per shape class —
    cached tuned winners when ``python -m repro.kernels.autotune`` (or
    ``kernel_bench --tune``) has run on this host, the static heuristic
    otherwise.  ``block_overrides`` pins configs per shape class
    (e.g. ``{"decode_m1": BlockConfig(1, 512)}``) ahead of both.
    """

    name = "pallas"

    def __init__(self, interpret: bool = False,
                 block_overrides: dict | None = None):
        self.interpret = interpret
        self.block_overrides = dict(block_overrides or {})

    def _blocks(self, m: int, *, expert: bool = False):
        """(block_m, block_n, exp_layout) or (None, None, None) to let
        ops.py resolve through the autotune table."""
        from repro.kernels import autotune
        cfg = self.block_overrides.get(
            autotune.shape_class(m, expert=expert))
        if cfg is None:
            return None, None, None
        return cfg.block_m, cfg.block_n, cfg.exp_layout

    def int_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        from repro.kernels.apsq_matmul import (
            apsq_matmul_int8,
            baseline_matmul_int8,
        )
        bm, bn, layout = self._blocks(int(x_codes.shape[0]))
        if psum_exps is None:
            return baseline_matmul_int8(x_codes, w_codes, n_p=1,
                                        block_m=bm, block_n=bn,
                                        interpret=self.interpret)
        return apsq_matmul_int8(x_codes, w_codes, psum_exps, gs=gs,
                                block_m=bm, block_n=bn, exp_layout=layout,
                                interpret=self.interpret)

    def int_expert_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        from repro.kernels.apsq_matmul import (
            apsq_expert_matmul_int8,
            baseline_expert_matmul_int8,
        )
        bm, bn, _ = self._blocks(int(x_codes.shape[1]), expert=True)
        if psum_exps is None:
            return baseline_expert_matmul_int8(
                x_codes, w_codes, n_p=1, block_m=bm, block_n=bn,
                interpret=self.interpret)
        return apsq_expert_matmul_int8(
            x_codes, w_codes, psum_exps, gs=gs, block_m=bm, block_n=bn,
            interpret=self.interpret)

    def kv_tile(self, seq_len, head_dim, rows):
        return kv_tile(seq_len, head_dim, rows,
                       self.block_overrides.get("prefill_attn"))

    def kv_attention(self, q, k_codes, v_codes, k_exp, v_exp, length):
        from repro.kernels.int8_kv_attention import int8_kv_attention
        s, hkv, hd = (int(d) for d in k_codes.shape[1:])
        rows = (int(q.shape[1]) if q.ndim == 4 else 1) * (
            int(q.shape[-2]) // hkv)
        return int8_kv_attention(q, k_codes, v_codes, k_exp, v_exp, length,
                                 block_s=self.kv_tile(s, hd, rows),
                                 interpret=self.interpret)


class AutoBackend(ExecBackend):
    """``pallas`` on TPU, ``oracle`` elsewhere (resolved at trace time).

    A convenience for CPU tests and examples, not the chip path: code that
    must run the kernels (``chip_smoke.py``, ``launch/serve.py --backend
    pallas``) names ``pallas``, which fails instead of falling back."""

    name = "auto"

    def resolve(self) -> ExecBackend:
        if jax.default_backend() == "tpu":
            return get_backend("pallas")
        return get_backend("oracle")

    def int_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        return self.resolve().int_gemm(x_codes, w_codes, psum_exps, gs=gs)

    def int_expert_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        return self.resolve().int_expert_gemm(x_codes, w_codes, psum_exps,
                                              gs=gs)

    def kv_attention(self, q, k_codes, v_codes, k_exp, v_exp, length):
        return self.resolve().kv_attention(q, k_codes, v_codes, k_exp,
                                           v_exp, length)

    def kv_tile(self, seq_len, head_dim, rows):
        return self.resolve().kv_tile(seq_len, head_dim, rows)


class ShardedBackend(ExecBackend):
    """Mesh-parallel integer execution: the local ``inner`` backend per
    shard, INT8-on-the-wire combines between shards.

    Wraps any leaf backend (``oracle``/``pallas``/an instance) and runs it
    inside ``repro.dist.shard_map`` over the mesh's ``model`` axis, with
    the shard axis chosen per layer by ``repro.dist.tp.plan_gemm`` from
    the same static shapes ``tp.shard_deployed`` placed the codes with:
    PSQ layers K-shard by whole PSUM tiles (int32 ``psum_scatter`` + int8
    code gather), APSQ layers column-parallel over N (lossless int8 code
    ``all_gather`` — the output is a code times the static ``2^e_last``),
    W8A8 K-shards with a full-precision int32 psum, MoE expert banks run
    expert-parallel with an int8 code gather as the all-to-all, and KV
    attention splits heads.  Every path is bit-exact to ``inner`` on one
    device; ``wire="fp32"`` swaps the int8 collectives for 4-byte gathers
    (identical results — the parity-debugging fallback ``dist_bench``
    prices the int8 path against).

    The registered ``backend="sharded"`` instance has no mesh and simply
    delegates to ``auto`` — construct ``ShardedBackend(mesh=...)`` (or
    pass ``mesh=`` to ``PagedServingEngine``, which wraps its backend
    automatically) for real multi-device serving.
    """

    name = "sharded"

    def __init__(self, mesh=None, inner="auto", *,
                 model_axis: str = "model", wire: str = "int8"):
        if wire not in ("int8", "fp32"):
            raise ValueError(f"wire must be 'int8' or 'fp32', got {wire!r}")
        self.mesh = mesh
        self.inner = inner
        self.model_axis = model_axis
        self.wire = wire

    def _leaf(self) -> ExecBackend:
        return get_backend(self.inner).resolve()

    def int_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        if self.mesh is None:
            return self._leaf().int_gemm(x_codes, w_codes, psum_exps, gs=gs)
        from repro.dist.tp import sharded_int_gemm  # lazy: dist -> kernels
        return sharded_int_gemm(self.mesh, self._leaf(), x_codes, w_codes,
                                psum_exps, gs=gs, model_axis=self.model_axis,
                                wire=self.wire)

    def int_expert_gemm(self, x_codes, w_codes, psum_exps, *, gs):
        if self.mesh is None:
            return self._leaf().int_expert_gemm(x_codes, w_codes, psum_exps,
                                                gs=gs)
        from repro.dist.tp import sharded_int_expert_gemm
        return sharded_int_expert_gemm(
            self.mesh, self._leaf(), x_codes, w_codes, psum_exps, gs=gs,
            model_axis=self.model_axis, wire=self.wire)

    def kv_attention(self, q, k_codes, v_codes, k_exp, v_exp, length):
        if self.mesh is None:
            return self._leaf().kv_attention(q, k_codes, v_codes, k_exp,
                                             v_exp, length)
        from repro.dist.tp import sharded_kv_attention
        return sharded_kv_attention(
            self.mesh, self._leaf(), q, k_codes, v_codes, k_exp, v_exp,
            length, model_axis=self.model_axis)

    def kv_tile(self, seq_len, head_dim, rows):
        return self._leaf().kv_tile(seq_len, head_dim, rows)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_backend(name: str, backend: ExecBackend) -> None:
    _REGISTRY[name] = backend


register_backend("oracle", OracleBackend())
register_backend("pallas", PallasBackend())
register_backend("auto", AutoBackend())
register_backend("sharded", ShardedBackend())

DEFAULT_BACKEND = "auto"


def available_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_backend(backend=None) -> ExecBackend:
    """Resolve a backend name / instance / None (-> the ``auto`` default)."""
    if backend is None:
        backend = DEFAULT_BACKEND
    if isinstance(backend, ExecBackend):
        return backend
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise KeyError(f"unknown exec backend {backend!r}; "
                       f"known: {available_backends()}") from None


# ---------------------------------------------------------------------------
# The one entry point the model zoo dispatches through
# ---------------------------------------------------------------------------

def quantize_activations(x2d: jax.Array, ax_exp: jax.Array,
                         a_bits: int = 8) -> jax.Array:
    """Float activations [M, K] -> INT8 codes at the PO2 scale 2^ax_exp
    (elementwise: any ``ax_exp`` that broadcasts against ``x2d``)."""
    qn, qp = qrange(a_bits, True)
    xf = x2d.astype(jnp.float32)
    return jnp.clip(jnp.round(xf * jnp.exp2(-ax_exp.astype(jnp.float32))),
                    qn, qp).astype(jnp.int8)


def execute_gemm(dq: DeployedQuantState, x: jax.Array, *,
                 backend=None) -> jax.Array:
    """Run one deployed linear: quantize -> integer GEMM -> rescale.

    ``x`` is [..., K] float; the result is [..., *dq.out_dims] in x.dtype.
    The leading dims are flattened to M (decode's [B, 1, C] becomes M=B,
    prefill's [B, T, C] becomes M=B*T) — the backend sees one [M, K] x
    [K, N] integer GEMM, pads to its block constraints (including ragged
    ``K % n_p`` via a zero-contribution remainder PSUM group), and the
    INT32 product-scale output is rescaled by ``2^(ax_exp + aw_exp)``.
    """
    backend = get_backend(backend).resolve()
    spec = dq.spec or QuantConfig.w8a8()
    k = dq.w_codes.shape[-2]
    out_shape = x.shape[:-1] + dq.out_dims
    gs = 1
    if dq.psum_exps is not None:
        n_p = int(dq.psum_exps.shape[0])
        gs = n_p if spec.psum.mode == "psq" else spec.psum.gs
    with jax.named_scope("apsq_gemm"):
        with jax.named_scope("quantize"):
            xc = quantize_activations(x.reshape(-1, k), dq.ax_exp,
                                      spec.a_bits)
        y = backend.int_gemm(xc, dq.w_codes, dq.psum_exps, gs=gs)
        with jax.named_scope("rescale"):
            scale = jnp.exp2((dq.ax_exp + dq.aw_exp).astype(jnp.float32))
            return (y.astype(jnp.float32) * scale).astype(
                x.dtype).reshape(out_shape)


def kv_tile(seq_len: int, head_dim: int, rows: int,
            pinned=None) -> int:
    """The KV tile of one INT8 KV-attention launch: cache positions per
    grid step, from shapes alone.

    One rule for the decode form (``rows`` = G query rows per KV head),
    the chunked form (C * G) and the replayed chunk: the ``prefill_attn``
    shape class resolves a request (``pinned`` -- a ``block_overrides``
    entry -- or the tuned winner, else the heuristic: up to 512 under
    the VMEM budget), snapped to the largest legal divisor of
    ``seq_len`` (``legal_block_s``).  A tile spans many pages, so the
    grid runs few steps per (slot, head); tiles past a slot's length
    are neither fetched nor computed.
    """
    from repro.kernels import autotune
    from repro.kernels.int8_kv_attention import legal_block_s
    cfg = pinned or autotune.get_block_config(rows, head_dim, seq_len,
                                              n_p=1, gs=1, attn=True)
    return legal_block_s(seq_len, cfg.block_n)


@jax.named_scope("kv_attention")
def execute_kv_attention(q: jax.Array, k_codes: jax.Array,
                         v_codes: jax.Array, k_exp: jax.Array,
                         v_exp: jax.Array, length: jax.Array, *,
                         backend=None) -> jax.Array:
    """Attention over an INT8 KV cache through the backend registry.

    q: [B, Hq, hd] float (decode: one row) or [B, C, Hq, hd] (prefill
    chunk: C causal rows ending at cache position ``length - 1``);
    k_codes/v_codes: [B, S, Hkv, hd] int8 with per-(batch, kv-head) PO2
    exponents [B, Hkv] int32; ``length`` [B] (or scalar) masks the valid
    cache prefix.  Returns output matching q's rank, in q's dtype.  This
    is the second op family beside ``execute_gemm``: the ``oracle``
    backend runs the shape-polymorphic jnp reference, the ``pallas``
    backend the flash-decode TPU kernel, whose KV tile follows from the
    shapes (``kv_tile``) in both forms.
    """
    backend = get_backend(backend).resolve()
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32),
                              (k_codes.shape[0],))
    return backend.kv_attention(
        q, k_codes, v_codes, k_exp.astype(jnp.int32),
        v_exp.astype(jnp.int32), length)


def backend_parity_check(dq: DeployedQuantState, x: jax.Array, *,
                         backends=None, reps: int = 1, warmup: int = 1):
    """Run one deployed GEMM through several backends, side by side.

    ``backends`` defaults to the oracle and the interpreted Pallas kernel
    (the CPU parity check).

    Returns ``(outs, times_us, bit_equal)``: per-backend outputs,
    per-backend wall-clock (jitted, post-warmup, microseconds), and
    whether every output is bit-identical to the first.  Shared by
    ``benchmarks/kernel_bench.py`` and the dry-run's per-cell
    ``backend_parity`` report so parity is measured one way everywhere.
    """
    import time

    import numpy as np

    if backends is None:
        backends = ("oracle", PallasBackend(interpret=True))
    outs, times = {}, {}
    for be in backends:
        resolved = get_backend(be)
        f = jax.jit(lambda a, _b=resolved: execute_gemm(dq, a, backend=_b))
        for _ in range(warmup):
            jax.block_until_ready(f(x))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(f(x))
        times[resolved.name] = (time.perf_counter() - t0) / reps * 1e6
        outs[resolved.name] = out
    vals = list(outs.values())
    bit_equal = all(np.array_equal(np.asarray(vals[0]), np.asarray(v))
                    for v in vals[1:])
    return outs, times, bit_equal


def execute_expert_gemm(dq: DeployedQuantState, x: jax.Array, *,
                        backend=None) -> jax.Array:
    """Stacked expert-bank GEMM: x [E, C, K] against per-expert codes.

    ``dq`` carries a leading expert axis on every data leaf (w_codes
    [E, K, N], ax_exp [E], aw_exp [E, ...], psum_exps [E, n_p, ...] — the
    per-expert exponent banks emitted by ``export_quantized``).  All E
    experts execute as ONE backend op: activations quantize per expert
    (each expert's PO2 shift broadcast over its rows), the backend's
    ``int_expert_gemm`` runs the stacked integer GEMM — a single fused
    ``pallas_call`` whose grid
    carries the expert axis on the Pallas backend, E oracle calls on the
    reference backend — and the INT32 outputs rescale per expert by
    ``2^(ax_exp[e] + aw_exp[e])``.  Bit-identical to slicing expert ``e``
    out of ``dq`` and calling ``execute_gemm`` on it (tests enforce).
    """
    backend = get_backend(backend).resolve()
    spec = dq.spec or QuantConfig.w8a8()
    n_exp = int(dq.w_codes.shape[0])
    k = int(dq.w_codes.shape[-2])
    out_shape = x.shape[:-1] + dq.out_dims
    gs = 1
    if dq.psum_exps is not None:
        n_p = int(dq.psum_exps.shape[1])
        gs = n_p if spec.psum.mode == "psq" else spec.psum.gs
    with jax.named_scope("apsq_gemm"):
        with jax.named_scope("quantize"):
            xc = quantize_activations(x.reshape(n_exp, -1, k),
                                      dq.ax_exp.reshape(n_exp, 1, 1),
                                      spec.a_bits)
        y = backend.int_expert_gemm(xc, dq.w_codes, dq.psum_exps, gs=gs)
        with jax.named_scope("rescale"):
            aw = dq.aw_exp
            aw = (aw.reshape(n_exp, 1, -1) if aw.ndim > 1
                  else aw.reshape(n_exp, 1, 1))
            scale = jnp.exp2((dq.ax_exp.reshape(n_exp, 1, 1) + aw)
                             .astype(jnp.float32))
            return (y.astype(jnp.float32) * scale).astype(
                x.dtype).reshape(out_shape)
