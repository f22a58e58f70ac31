"""Quantized linear layers — typed per-layer quantizer state (API v2).

Every model in the zoo funnels its projection GEMMs through ``quant_dense``
so that enabling W8A8 + PSUM quantization (PSQ/APSQ, any group size) is a
pure config change, exactly as the paper integrates APSQ into QAT (§IV-A).

The quantizer state of one linear is a registered-pytree dataclass,
``QuantState``, carrying its learned scales (data) plus its *resolved*
``QuantConfig`` and a stable layer name (static metadata).  Because the
spec travels with the state, ``quant_dense`` needs no global config: a
per-layer ``QuantPolicy`` (``repro.quant.policy``) resolves a different
``gs``/``n_p``/bits per layer at init time and the apply path just follows
the state.  ``QuantState`` supports dict-style reads (``qp["ap"]``,
``"ap" in qp``) for compatibility with the legacy ``{"aw","ax","ap"}``
dicts, which ``quant_dense`` still accepts alongside an explicit config.

Calibration is capture-based and functional: ``quant_dense`` takes an
optional ``tap`` list and appends a ``TapRecord`` (name, inputs, weights,
state) whenever it executes eagerly — no monkey-patching, and
``repro.quant.calibrate_model`` reaches linears inside ``lax.scan`` bodies
by slicing scan-stacked params and running per-unit capture passes.

Fake-quant semantics (QAT): weights/activations through LSQ [10]; PSUMs
through PO2-scale quantizers via Algorithm 1.  Deployment is
``DeployedQuantState`` (INT8 weight codes + PO2 shift exponents, produced
by ``repro.quant.export.export_quantized``), executed here with the
true-integer semantics of ``repro.kernels.apsq_matmul``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .apsq import apsq_matmul
from .quantizers import (
    init_alpha_from,
    lsq_quantize,
    qrange,
)

PSUM_MODES = ("none", "psq", "apsq")


@dataclasses.dataclass(frozen=True)
class PsumQuantConfig:
    """PSUM handling for the simulated IS/WS accelerator."""

    mode: str = "none"  # none | psq | apsq
    gs: int = 2         # group size (Algorithm 1); psq == apsq with gs>=n_p
    n_p: int = 8        # simulated #PSUM tiles along K (= ceil(C_i/P_ci))
    bits: int = 8

    def __post_init__(self):
        if self.mode not in PSUM_MODES:
            raise ValueError(f"psum mode must be one of {PSUM_MODES}")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """W8A8 fake-quant + optional PSUM quantization."""

    enabled: bool = False
    w_bits: int = 8
    a_bits: int = 8
    per_channel_w: bool = True
    psum: PsumQuantConfig = dataclasses.field(default_factory=PsumQuantConfig)

    @staticmethod
    def w8a8() -> "QuantConfig":
        return QuantConfig(enabled=True)

    @staticmethod
    def apsq(gs: int = 2, n_p: int = 8) -> "QuantConfig":
        return QuantConfig(enabled=True, psum=PsumQuantConfig("apsq", gs=gs, n_p=n_p))

    @staticmethod
    def psq(n_p: int = 8) -> "QuantConfig":
        return QuantConfig(enabled=True, psum=PsumQuantConfig("psq", n_p=n_p))


def effective_n_p(k: int, requested: int) -> int:
    """Largest divisor of K that is <= requested (K-tiling must be exact)."""
    n = max(1, min(requested, k))
    while k % n:
        n -= 1
    return n


# ---------------------------------------------------------------------------
# Typed quantizer state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantState:
    """Quantizer state of one linear: learned scales + resolved spec.

    Data (pytree leaves): ``aw`` (LSQ weight scale, per-channel [N] or
    scalar), ``ax`` (LSQ activation scale, scalar), ``ap`` (PO2 log2
    PSUM scales, [n_p]; None when ``spec.psum.mode == "none"``).
    Static metadata: ``spec`` (the per-layer resolved ``QuantConfig``,
    with ``psum.n_p`` already clamped to a divisor of K) and ``name``
    (the stable layer name used by policies, taps, and export).
    """

    aw: jax.Array
    ax: jax.Array
    ap: jax.Array | None = None
    spec: QuantConfig | None = None
    name: str = ""

    # dict-style reads for legacy ``qp["ap"]`` call sites
    _FIELDS = ("aw", "ax", "ap")

    def __getitem__(self, key):
        if key in self._FIELDS:
            v = getattr(self, key)
            if v is None:
                raise KeyError(key)
            return v
        raise KeyError(key)

    def __contains__(self, key):
        return key in self._FIELDS and getattr(self, key) is not None

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def as_dict(self) -> dict:
        d = {"aw": self.aw, "ax": self.ax}
        if self.ap is not None:
            d["ap"] = self.ap
        return d

    @staticmethod
    def from_dict(d: dict, spec: QuantConfig | None = None,
                  name: str = "") -> "QuantState":
        return QuantState(aw=d["aw"], ax=d["ax"], ap=d.get("ap"),
                          spec=spec, name=name)


jax.tree_util.register_dataclass(
    QuantState, data_fields=("aw", "ax", "ap"), meta_fields=("spec", "name"))


@dataclasses.dataclass(frozen=True)
class DeployedQuantState:
    """Integer deployment view of one linear (output of ``export_quantized``).

    Data: ``w_codes`` (INT8 weight codes [K, N]), ``ax_exp`` (activation
    PO2 exponent, scalar int32), ``aw_exp`` (weight PO2 exponents, [N] or
    scalar int32), ``psum_exps`` (PSUM shift exponents in product-scale
    units, [n_p] or [n_p, N] int32; None for plain W8A8).
    Static: ``spec``, ``name``, ``out_dims`` (original trailing weight
    dims, for the output reshape).

    Executed by ``quant_dense``/``deployed_dense`` with the exact integer
    semantics of ``repro.kernels.apsq_matmul`` (shift-based quant/dequant,
    round-half-up) — scan-stackable like any other param subtree.
    """

    w_codes: jax.Array
    ax_exp: jax.Array
    aw_exp: jax.Array
    psum_exps: jax.Array | None = None
    spec: QuantConfig | None = None
    name: str = ""
    out_dims: tuple = ()


jax.tree_util.register_dataclass(
    DeployedQuantState,
    data_fields=("w_codes", "ax_exp", "aw_exp", "psum_exps"),
    meta_fields=("spec", "name", "out_dims"))


@dataclasses.dataclass
class TapRecord:
    """One captured linear invocation (calibration capture API)."""

    name: str
    x: jax.Array    # [tokens, K] activations as seen by the linear
    w: jax.Array    # [K, N] flattened weight
    qp: "QuantState"


def _spec_of(qp, cfg: QuantConfig | None) -> QuantConfig | None:
    if isinstance(qp, QuantState) and qp.spec is not None:
        return qp.spec
    return cfg


# ---------------------------------------------------------------------------
# Init / calibration
# ---------------------------------------------------------------------------

def quant_params_init(w: jax.Array, cfg: QuantConfig,
                      name: str = "") -> QuantState:
    """Quantizer state for one linear with (flattened) weight [K, N]."""
    k = w.shape[0]
    n = int(w.size // k)
    w2d = w.reshape(k, n)
    if cfg.per_channel_w:
        _, qp = qrange(cfg.w_bits, True)
        aw = 2.0 * jnp.mean(jnp.abs(w2d), axis=0) / math.sqrt(qp) + 1e-12
    else:
        aw = init_alpha_from(w2d, cfg.w_bits)
    ap = None
    spec = cfg
    if cfg.psum.mode != "none":
        n_p = effective_n_p(k, cfg.psum.n_p)
        spec = dataclasses.replace(
            cfg, psum=dataclasses.replace(cfg.psum, n_p=n_p))
        # PSUM scales start at a generic magnitude; ``calibrate_dense``
        # refines them from data (running-accumulation statistics).
        ap = jnp.zeros((n_p,), jnp.float32) + jnp.log2(jnp.asarray(16.0))
    return QuantState(aw=aw, ax=jnp.asarray(1.0, jnp.float32), ap=ap,
                      spec=spec, name=name)


def calibrate_dense(qp, x: jax.Array, w: jax.Array,
                    cfg: QuantConfig | None = None):
    """Refine activation & PSUM scales from a calibration batch.

    PSUM scales are initialized from the *running accumulation* magnitude
    (cumsum over tiles) — the quantity APSQ actually quantizes — so early
    tiles get small scales and late tiles get large ones.  Accepts a
    ``QuantState`` (config taken from its spec) or a legacy dict + config.
    """
    spec = _spec_of(qp, cfg)
    if spec is None:
        raise ValueError("calibrate_dense needs a QuantState with a spec "
                         "or an explicit QuantConfig")
    k = w.shape[0]
    n = int(w.size // k)
    w2d = w.reshape(k, n).astype(jnp.float32)
    x2d = x.reshape(-1, k).astype(jnp.float32)
    ax = init_alpha_from(x2d, spec.a_bits)
    ap = qp.get("ap") if isinstance(qp, (QuantState, dict)) else None
    if ap is not None:
        n_p = ap.shape[-1]
        kt = k // n_p
        tiles = jnp.einsum(
            "bpk,pkn->pbn",
            x2d.reshape(-1, n_p, kt),
            w2d.reshape(n_p, kt, n),
        )
        running = jnp.cumsum(tiles, axis=0)
        _, qpmax = qrange(spec.psum.bits, True)
        mags = 2.0 * jnp.mean(jnp.abs(running), axis=(1, 2)) / math.sqrt(qpmax)
        ap = jnp.log2(jnp.maximum(mags, 1e-6))
    if isinstance(qp, QuantState):
        return dataclasses.replace(qp, ax=ax, ap=ap)
    out = dict(qp)
    out["ax"] = ax
    if ap is not None:
        out["ap"] = ap
    return out


# ---------------------------------------------------------------------------
# Fake-quant (QAT) execution
# ---------------------------------------------------------------------------

def quant_dense(
    x: jax.Array,
    w: jax.Array,
    qp,
    cfg: QuantConfig | None = None,
    *,
    tap: list | None = None,
    backend=None,
) -> jax.Array:
    """``x @ w`` with optional W8A8 fake quant and PSQ/APSQ PSUM handling.

    x: [..., K];  w: [K, ...] (trailing dims flattened to N internally).
    ``qp`` is a ``QuantState`` (spec self-carried), a legacy
    ``{"aw","ax","ap"}`` dict (spec from ``cfg``), or a
    ``DeployedQuantState`` (integer path; ``w`` is ignored).
    ``tap``: optional capture list — when executing eagerly, a
    ``TapRecord`` for this linear is appended (calibration capture API).
    ``backend``: execution backend for the deployed integer path
    (``repro.exec``; name, instance, or None for the ``auto`` default).
    Returns [..., *w.shape[1:]] in x.dtype.
    """
    if isinstance(qp, DeployedQuantState):
        return deployed_dense(x, qp, backend=backend)
    spec = _spec_of(qp, cfg)
    out_shape = x.shape[:-1] + w.shape[1:]
    if spec is None or not spec.enabled or qp is None:
        y = jax.lax.dot_general(
            x, w.reshape(w.shape[0], -1).astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
        )
        return y.reshape(out_shape)

    k = w.shape[0]
    w2d = w.reshape(k, -1)
    if (tap is not None and isinstance(qp, QuantState)
            and not isinstance(x, jax.core.Tracer)):
        tap.append(TapRecord(qp.name, x.reshape(-1, k), w2d, qp))
    in_dtype = x.dtype
    xf = x.astype(jnp.float32)
    wf = w2d.astype(jnp.float32)
    xq = lsq_quantize(xf, qp["ax"], bits=spec.a_bits)
    wq = lsq_quantize(wf, qp["aw"], bits=spec.w_bits)

    mode = spec.psum.mode
    if mode == "none":
        y = jax.lax.dot_general(
            xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        # Gather the FSDP(K)-shard of the weight ONCE before the PSUM tile
        # loop, KEEPING the TP(N) shard: without this every one of the n_p
        # tile GEMMs contracts a data-sharded K slice and all-reduces its
        # partial sums — n_p x the collective bytes of the unquantized
        # GEMM.  Full replication (P(None, None)) was measured and
        # REFUTED — it drags replicated weights/grads through the scan
        # residuals (§Perf it2/it3 on the APSQ cell).
        try:
            wq = jax.lax.with_sharding_constraint(
                wq, jax.sharding.PartitionSpec(None, "model"))
        except (ValueError, RuntimeError):
            pass  # no ambient mesh (unsharded smoke/QAT runs)
        n_p = qp["ap"].shape[0]
        gs = n_p if mode == "psq" else spec.psum.gs
        y = apsq_matmul(xq, wq, qp["ap"], n_p=n_p, gs=gs, bits=spec.psum.bits)
    return y.astype(in_dtype).reshape(out_shape)


# ---------------------------------------------------------------------------
# Integer deployment execution
# ---------------------------------------------------------------------------

def tied_head_weight(table: jax.Array) -> jax.Array:
    """The tied-embedding logits weight: table [V, ...D] -> [D, V] fp32.

    One definition shared by head calibration (``quant.qat``), integer
    export (``quant.export``), and the fake-quant forward
    (``models.model.logits_from_hidden``) — the three views must see the
    identical matrix or the calibrated scales/codes stop matching the
    GEMM actually executed.
    """
    return table.reshape(table.shape[0], -1).T.astype(jnp.float32)


def deployed_dense(x: jax.Array, dq: DeployedQuantState, *,
                   backend=None) -> jax.Array:
    """Integer GEMM on exported codes, semantics of ``kernels/apsq_matmul``.

    Activations are quantized to INT8 at the PO2 scale ``2^ax_exp``; the
    INT32 PSUM tiles follow Algorithm 1 with shift exponents ``psum_exps``
    in product-scale units (per-tile, or per-(tile, column) when weights
    are per-channel); the result is rescaled to float.

    The actual integer GEMM is dispatched through the ``repro.exec``
    backend registry: ``oracle`` (pure jnp, runs under jit/scan/vmap),
    ``pallas`` (the compiled kernel; ``PallasBackend(interpret=True)`` on
    CPU), or ``auto`` (default: pallas on TPU, oracle elsewhere) — all
    bit-identical.
    """
    from repro.exec import execute_gemm  # lazy: exec imports kernels

    return execute_gemm(dq, x, backend=backend)
