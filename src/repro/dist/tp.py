"""Tensor/expert-parallel placement + collectives for deployed integer models.

This module is the serving-side mesh story (ROADMAP item 3): it decides,
per exported ``DeployedQuantState``, how the INT8 code banks are split over
the ``model`` mesh axis, and provides the shard_map bodies the ``sharded``
exec backend (``repro.exec.ShardedBackend``) runs — with INT8-on-the-wire
combines wherever the PO2-grid invariant makes them lossless.

Shard rules (``plan_gemm``) — derived from Algorithm-1 semantics, not
preference, and shared verbatim by placement and execution so both always
agree:

  * **PSQ** (``gs >= n_p``): every PSUM tile except the final one is
    quantized *independently* (carry-free), so the K axis shards into
    whole-PSUM-tile spans — each device owns ``n_p/D`` contiguous tiles of
    codes, quantizes/dequantizes them locally on the PO2 grid, and the
    INT32 partials combine exactly.  A ragged ``K % n_p`` remainder group
    (zero-padded, contributes nothing) always falls inside the LAST
    device's span because tile order is preserved.  On the int8 wire path
    the combine is ``psum_scatter`` (int32) + final-quantize per N-slice +
    int8 code ``all_gather`` — 5 bytes/elt vs 8 for the full-precision
    psum.  Requires ``n_p % D == 0``.
  * **APSQ** (``gs < n_p``): the group-start code chain
    ``stored[i] = Q(tiles[i] + sum deq(stored[i-gs..i]))`` is *sequential
    along K* — a K-shard cannot reproduce it without a device-serial carry
    pipeline, and quantizing INT32 partials for the wire would break
    bit-exactness.  So APSQ layers shard **N** (column-parallel): each
    device runs the full recurrence on its column slice, and because the
    layer's final output is by construction an INT8 code times the static
    ``2^e_last``, the combine is a *lossless* INT8 ``all_gather`` of codes
    (arithmetic right-shift by ``e_last``, gather, left-shift) — exactly
    4x fewer wire bytes than an fp32/int32 gather.  Requires
    ``N % D == 0``.
  * **W8A8** (``psum_exps is None``): plain INT32 accumulation — K-shards
    by even column spans with an exact int32 ``psum`` (full precision on
    the wire on both paths; quantizing the partials would be lossy and is
    refused).
  * **MoE expert banks**: the stacked expert axis shards over ``model``
    (EP).  Dispatch needs no collective — activations are replicated over
    ``model`` and each device slices its experts' rows — and the combine
    gathers per-expert *outputs as INT8 codes* (each expert's ``e_last``
    is static), so the all-to-all-equivalent moves 1 byte/elt.
  * Anything that misses its divisibility constraint falls back
    (psq -> "n" -> replicate) rather than erroring; ``LayerPlan.axis ==
    "replicate"`` layers run the single-device path unchanged.

Exponent banks: the big data — ``[K, N]`` code banks — shard; the
``[n_p]``/``[n_p, N]`` PSUM exponent banks stay REPLICATED everywhere
(they are noise next to the codes: ``n_p x N`` int32 vs ``K x N`` int8).
Column-parallel and expert-parallel bodies slice their local span from
the replicated bank at trace time, so the full ``e_last`` needed to
finish the INT8 code gather is already resident — no per-call exponent
sidecar ever crosses the wire (at decode ``m = 1`` a ``4 x N`` sidecar
would cost more than the code gather it annotates).

``shard_deployed`` walks an exported tree, ``device_put``s every leaf with
its ``NamedSharding``, and returns a ``{name: LayerPlan}`` report whose
``wire_bytes(m)`` is computed *analytically* from static shapes — this is
what ``benchmarks/dist_bench.py`` aggregates, so the int8-vs-fp32 wire
accounting can't drift from the placement that actually ran.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import DeployedQuantState, QuantConfig
from repro.kernels.apsq_matmul.ref import dequantize_psum, quantize_psum

from .sharding import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``repro.dist.shard_map`` adapted for the serving collectives.

    Differences from the raw wrapper, both forced by how these bodies
    are used:

      * manual over EVERY mesh axis, not just ``model`` — the bodies use
        ``axis_index``, which lowers to a ``PartitionId`` op that GSPMD
        refuses to partition when other axes (a multi-pod mesh's "pod"/
        "data") stay auto.  Serving replicates all tensors over those
        axes, so full-manual is semantically identical: unmentioned axes
        in the specs mean replicated slices.
      * wrapped in ``jit`` — partial- and full-manual shard_map is only
        implemented under a trace; the engines always jit these, but the
        backend ops are public API and must also work eagerly.  Under an
        outer jit the inner one is inlined, no double dispatch.

    ``axis_names`` is accepted (call sites name the collective axis) but
    widened to the full mesh.
    """
    del axis_names
    return jax.jit(_shard_map(f, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs,
                              axis_names=set(mesh.axis_names)))

# ---------------------------------------------------------------------------
# The shared placement/execution decision
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """How one [M, K] x [K, N] deployed GEMM splits over D shards."""

    axis: str   # "k" | "n" | "expert" | "replicate"
    mode: str   # "w8a8" | "psq" | "apsq"
    d: int

    @property
    def sharded(self) -> bool:
        return self.axis != "replicate" and self.d > 1


def gemm_mode(n_p: int | None, gs: int) -> str:
    """Mode from the exponent-bank geometry (what the kernel actually runs,
    regardless of what the spec *declares* — gs >= n_p executes as PSQ)."""
    if n_p is None:
        return "w8a8"
    return "psq" if gs >= n_p else "apsq"


def plan_gemm(*, k: int, n: int, n_p: int | None, gs: int,
              d: int) -> GemmPlan:
    """Pick the shard axis for one GEMM.  Pure + static: the ShardedBackend
    re-derives this at trace time from the same shapes ``shard_deployed``
    placed with, so placement and execution cannot disagree."""
    mode = gemm_mode(n_p, gs)
    if d <= 1:
        return GemmPlan("replicate", mode, d)
    if mode == "psq" and n_p % d == 0 and n_p >= d:
        return GemmPlan("k", mode, d)
    if mode == "w8a8" and k % d == 0:
        return GemmPlan("k", mode, d)
    if n % d == 0:
        return GemmPlan("n", mode, d)
    return GemmPlan("replicate", mode, d)


def _dq_geometry(dq: DeployedQuantState, kind: str):
    """(k, n, n_p, gs, lead, units, experts) per-unit geometry of one bank.

    ``lead`` = leading axes before the per-unit [K, N]: scan stacking adds
    one, the expert axis adds one.  Stacking is detected from ``ax_exp``'s
    rank (scalar per plain linear, [E] per expert bank).
    """
    base = 1 if kind == "expert" else 0
    stacked = dq.ax_exp.ndim > base
    lead = base + (1 if stacked else 0)
    k, n = int(dq.w_codes.shape[-2]), int(dq.w_codes.shape[-1])
    units = int(dq.w_codes.shape[0]) if stacked else 1
    experts = int(dq.w_codes.shape[lead - 1]) if kind == "expert" else 1
    n_p = None
    gs = 1
    if dq.psum_exps is not None:
        n_p = int(dq.psum_exps.shape[lead])
        spec = dq.spec or QuantConfig.w8a8()
        gs = n_p if spec.psum.mode == "psq" else spec.psum.gs
    return k, n, n_p, gs, lead, units, experts


# ---------------------------------------------------------------------------
# Wire accounting (analytic, from the static plan)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LayerPlan:
    """One placed layer: shard decision + analytic wire-byte model.

    Byte convention (both paths, so the ratio is meaningful):
    ``all_gather`` of a logical payload moves payload x itemsize;
    ``psum`` moves 2 x payload x 4 (reduce-scatter + all-gather halves);
    ``psum_scatter`` alone moves payload x 4.  Exponent banks are
    replicated at placement time, so no sidecar term appears.
    """

    name: str
    kind: str        # "linear" | "head" | "expert" | "attn"
    mode: str        # "w8a8" | "psq" | "apsq" | "-"
    axis: str        # "k" | "n" | "expert" | "heads" | "replicate"
    d: int
    k: int = 0
    n: int = 0
    n_p: int | None = None
    gs: int = 1
    units: int = 1
    experts: int = 1
    per_col: bool = False

    def wire_bytes(self, m: int) -> dict:
        """{"int8": bytes, "fp32": bytes} for one call with m rows
        (per expert, for expert banks) under each wire mode."""
        if self.axis == "replicate" or self.d <= 1:
            return {"int8": 0, "fp32": 0}
        payload = self.units * self.experts * m * self.n
        if self.kind == "attn":
            b = payload * 4          # fp32 head gather, identical both paths
            return {"int8": b, "fp32": b}
        if self.mode == "w8a8":
            b = 8 * payload if self.axis == "k" else 4 * payload
            return {"int8": b, "fp32": b}
        if self.axis == "k":         # PSQ: int32 scatter + int8 code gather
            return {"int8": 5 * payload, "fp32": 8 * payload}
        # column-parallel / expert-parallel PSUM-mode: lossless code gather
        return {"int8": payload, "fp32": 4 * payload}


def wire_report(plans: dict, m: int = 1) -> dict:
    """Aggregate ``LayerPlan.wire_bytes`` over a plan dict.

    ``switchable`` sums only the collectives the wire flag actually
    changes (PSUM-mode combines); ``total`` includes the flag-invariant
    ones (w8a8 psums, attention head gathers) so nothing is hidden.
    """
    layers, tot8, tot32, sw8, sw32 = {}, 0, 0, 0, 0
    for name, pl in plans.items():
        b = pl.wire_bytes(m)
        layers[name] = {"axis": pl.axis, "mode": pl.mode, **b}
        tot8 += b["int8"]
        tot32 += b["fp32"]
        if b["int8"] != b["fp32"]:
            sw8 += b["int8"]
            sw32 += b["fp32"]
    return {
        "m": m,
        "layers": layers,
        "total": {"int8": tot8, "fp32": tot32,
                  "ratio": (tot32 / tot8) if tot8 else None},
        "switchable": {"int8": sw8, "fp32": sw32,
                       "ratio": (sw32 / sw8) if sw8 else None},
    }


# ---------------------------------------------------------------------------
# Placement: shard_deployed / shard_paged_state
# ---------------------------------------------------------------------------


def _mesh_dim(mesh, model_axis: str) -> int:
    return int(mesh.shape[model_axis]) if model_axis in mesh.axis_names else 1


def _put(leaf, mesh, spec: P):
    return jax.device_put(leaf, NamedSharding(mesh, spec))


def _place_dq(dq: DeployedQuantState, kind: str, mesh, ax: str,
              plans: dict) -> DeployedQuantState:
    d = _mesh_dim(mesh, ax)
    k, n, n_p, gs, lead, units, experts = _dq_geometry(dq, kind)
    per_col = dq.psum_exps is not None and dq.psum_exps.ndim - lead == 2
    pad = (None,) * lead

    if kind == "expert":
        plan_axis = "expert" if (d > 1 and experts % d == 0) else "replicate"
        mode = gemm_mode(n_p, gs)
        e_ax = (None,) * (lead - 1) + (ax,)
        if plan_axis == "expert":
            w_spec = P(*e_ax, None, None)
            scalar_spec = P(*e_ax)
            # exponent bank replicated: the EP body slices its experts'
            # rows locally and still holds every expert's e_last for the
            # post-gather left-shift (no per-call exponent collective)
            exp_spec = None if dq.psum_exps is None else P()
            aw_spec = P(*e_ax, *(None,) * (dq.aw_exp.ndim - lead))
        else:
            w_spec = scalar_spec = aw_spec = P()
            exp_spec = None if dq.psum_exps is None else P()
    else:
        plan = plan_gemm(k=k, n=n, n_p=n_p, gs=gs, d=d)
        plan_axis, mode = plan.axis, plan.mode
        scalar_spec = P(*pad) if dq.ax_exp.ndim else P()
        aw_spec = P(*(None,) * dq.aw_exp.ndim)
        exp_spec = (None if dq.psum_exps is None
                    else P(*(None,) * dq.psum_exps.ndim))
        if plan_axis == "k" and k % d == 0:
            # PSQ tile spans / w8a8 column spans; ragged K (k % n_p != 0)
            # keeps replicated storage — execution pads and slices.
            w_spec = P(*pad, ax, None)
        elif plan_axis == "n":
            # exponent bank stays replicated even for per-column [n_p, N]
            # layers: the body slices its columns locally, and the full
            # e_last row finishes the code gather with no sidecar.
            w_spec = P(*pad, None, ax)
        else:
            w_spec = P(*pad, None, None)

    name = dq.name or f"dq{len(plans)}"
    plans[name] = LayerPlan(name=name, kind=kind, mode=mode, axis=plan_axis,
                            d=d, k=k, n=n, n_p=n_p, gs=gs, units=units,
                            experts=experts, per_col=per_col)
    return dataclasses.replace(
        dq,
        w_codes=_put(dq.w_codes, mesh, w_spec),
        ax_exp=_put(dq.ax_exp, mesh, scalar_spec),
        aw_exp=_put(dq.aw_exp, mesh, aw_spec),
        psum_exps=(None if dq.psum_exps is None
                   else _put(dq.psum_exps, mesh, exp_spec)),
    )


def shard_deployed(tree, mesh, *, model_axis: str = "model"):
    """Partition an exported param tree over ``mesh``'s model axis.

    Every ``DeployedQuantState`` is placed per ``plan_gemm`` (PSQ -> K by
    whole PSUM tiles, APSQ -> N, W8A8 -> K, MoE expert banks -> expert
    axis); float leaves (norms, router, embedding table) replicate.
    Returns ``(tree, plans)`` — the committed-device tree plus the
    ``{name: LayerPlan}`` wire report feeding ``dist_bench``.
    """
    plans: dict = {}

    def walk(node):
        if isinstance(node, DeployedQuantState):
            return _place_dq(node, "linear", mesh, model_axis, plans)
        if isinstance(node, dict):
            is_moe = "router" in node
            out = {}
            for key, v in node.items():
                if isinstance(v, DeployedQuantState):
                    kind = ("head" if key == "qp_head" else
                            "expert" if is_moe and key != "qp" else "linear")
                    out[key] = _place_dq(v, kind, mesh, model_axis, plans)
                else:
                    out[key] = walk(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        if node is None:
            return None
        return _put(node, mesh, P())

    return walk(tree), plans


def shard_paged_state(state, cfg, mesh, *, model_axis: str = "model"):
    """Place a paged decode state: KV pools shard over kv-heads on the
    model axis (``[n_pages, P, Hkv, hd]`` -> ``P(None, None, ax, None)``),
    running exponents ``[B, Hkv]`` follow, everything else replicates.

    Head sharding needs the axis to divide BOTH head counts (the attention
    shard_map splits q over Hq and the pools over Hkv); otherwise the
    whole state replicates and attention runs single-device semantics.
    Returns ``(state, plans)`` with one "attn" LayerPlan per attention
    layer for the (flag-invariant) fp32 head-gather accounting.
    """
    d = _mesh_dim(mesh, model_axis)
    shard_heads = (d > 1 and cfg.n_heads % d == 0 and cfg.n_kv_heads % d == 0)
    plans: dict = {}

    def spec(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        key = names[-1] if names else ""
        if shard_heads and key in ("k_pages", "v_pages"):
            if key == "k_pages":
                i = len(plans)
                plans[f"attn.{i}"] = LayerPlan(
                    name=f"attn.{i}", kind="attn", mode="-", axis="heads",
                    d=d, n=cfg.n_heads * cfg.hd)
            return P(*(None,) * (leaf.ndim - 2), model_axis, None)
        if shard_heads and key in ("k_exp", "v_exp"):
            return P(*(None,) * (leaf.ndim - 1), model_axis)
        return P()

    placed = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _put(leaf, mesh, spec(path, leaf)), state)
    return placed, plans


# ---------------------------------------------------------------------------
# Collective GEMM bodies (called by repro.exec.ShardedBackend)
# ---------------------------------------------------------------------------


def _gather_codes(y_local: jax.Array, e_local: jax.Array, e_full: jax.Array,
                  e_is_col: bool, ax: str, axis: int) -> jax.Array:
    """Lossless INT8 gather of a PSUM-mode output along ``axis``.

    ``y_local`` is ``code << e_last`` by Algorithm-1 construction (code in
    [-128, 127]), so the arithmetic right-shift recovers the code exactly;
    ONLY 1-byte codes cross the wire — ``e_full`` is the replicated
    exponent bank's last row, already resident on every device, and the
    left-shift after the gather is exact.
    """
    eb = e_local
    if e_is_col:  # broadcast [.., N_loc] exps over the M rows
        eb = jnp.expand_dims(e_local, axis=-2)
    codes = jnp.right_shift(y_local, jnp.asarray(eb, jnp.int32))
    codes = jax.lax.all_gather(codes.astype(jnp.int8), ax,
                               axis=axis, tiled=True)
    ebf = jnp.expand_dims(e_full, -2) if e_is_col else e_full
    return jnp.left_shift(codes.astype(jnp.int32), jnp.asarray(ebf, jnp.int32))


def sharded_int_gemm(mesh, inner, x_codes, w_codes, psum_exps, *, gs: int,
                     model_axis: str = "model", wire: str = "int8"):
    """Mesh-parallel ``int_gemm`` with plan-directed sharding + combines.

    Bit-exact to ``inner.int_gemm`` on one device by construction: K-shards
    only ever move full-precision INT32 partials (or finished PO2-grid
    codes), N-shards only move finished codes.  ``wire="fp32"`` keeps the
    identical arithmetic but gathers 4-byte words — the parity-debugging
    fallback (and the baseline ``dist_bench`` prices).
    """
    m, k = int(x_codes.shape[0]), int(x_codes.shape[1])
    n = int(w_codes.shape[1])
    d = _mesh_dim(mesh, model_axis)
    n_p = None if psum_exps is None else int(psum_exps.shape[0])
    plan = plan_gemm(k=k, n=n, n_p=n_p, gs=gs, d=d)
    if not plan.sharded:
        return inner.int_gemm(x_codes, w_codes, psum_exps, gs=gs)
    ax = model_axis
    per_col = psum_exps is not None and psum_exps.ndim == 2

    if plan.axis == "n":
        nloc = n // d

        def body_n(xc, w_loc, e_full):
            # exponent bank arrives replicated; per-column layers slice
            # their own column span at trace time (free, no collective)
            if psum_exps is None:
                e_loc = None
            elif per_col:
                idx = jax.lax.axis_index(ax)
                e_loc = jax.lax.dynamic_slice_in_dim(
                    e_full, idx * nloc, nloc, axis=1)
            else:
                e_loc = e_full
            y = inner.int_gemm(xc, w_loc, e_loc, gs=gs)
            if psum_exps is None or wire == "fp32":
                return jax.lax.all_gather(y, ax, axis=1, tiled=True)
            return _gather_codes(y, e_loc[-1], e_full[-1], per_col,
                                 ax, axis=1)

        e_spec = (P() if psum_exps is None
                  else P(None, None) if per_col else P(None))
        e_arg = jnp.zeros((), jnp.int32) if psum_exps is None else psum_exps
        f = shard_map(body_n, mesh=mesh,
                      in_specs=(P(None, None), P(None, ax), e_spec),
                      out_specs=P(None, None), axis_names={ax})
        return f(x_codes, w_codes, e_arg)

    # K-sharded
    if plan.mode == "w8a8":
        def body_k8(x_loc, w_loc):
            part = inner.int_gemm(x_loc, w_loc, None, gs=1)
            return jax.lax.psum(part, ax)

        f = shard_map(body_k8, mesh=mesh,
                      in_specs=(P(None, ax), P(ax, None)),
                      out_specs=P(None, None), axis_names={ax})
        return f(x_codes, w_codes)

    # PSQ: whole-PSUM-tile spans.  Pad ragged K to n_p * kt first (the
    # zero-contribution remainder group lands in the LAST device's span).
    kt = -(-k // n_p)
    kpad = n_p * kt - k
    if kpad:
        x_codes = jnp.pad(x_codes, ((0, 0), (0, kpad)))
        w_codes = jnp.pad(w_codes, ((0, kpad), (0, 0)))
    tpd = n_p // d
    scatter = wire == "int8" and n % d == 0

    def body_kpsq(x_loc, w_loc, exps):
        idx = jax.lax.axis_index(ax)
        xt = x_loc.reshape(m, tpd, kt).transpose(1, 0, 2)
        wt = w_loc.reshape(tpd, kt, n)
        tiles = inner.int_expert_gemm(xt, wt, None, gs=1)  # [tpd, M, N]
        e_loc = jax.lax.dynamic_slice_in_dim(exps, idx * tpd, tpd, axis=0)
        eb = (e_loc[:, None, :] if per_col else e_loc[:, None, None])
        q = dequantize_psum(quantize_psum(tiles, eb), eb)
        # The globally-final tile stays raw INT32 (Algorithm 1 quantizes
        # it only once, after the full accumulation).
        is_last = idx == d - 1
        tail = jnp.where(is_last, tiles[-1], q[-1])
        partial = tail + (q[:-1].sum(axis=0) if tpd > 1 else 0)
        e_last = exps[-1]
        if scatter:
            part = jax.lax.psum_scatter(partial, ax, scatter_dimension=1,
                                        tiled=True)
            nloc = n // d
            e_sl = (jax.lax.dynamic_slice_in_dim(e_last, idx * nloc, nloc, 0)
                    if per_col else e_last)
            codes = quantize_psum(part, e_sl)
            codes = jax.lax.all_gather(codes, ax, axis=1, tiled=True)
            return dequantize_psum(codes, e_last)
        total = jax.lax.psum(partial, ax)
        return dequantize_psum(quantize_psum(total, e_last), e_last)

    f = shard_map(body_kpsq, mesh=mesh,
                  in_specs=(P(None, ax), P(ax, None),
                            P(None, None) if per_col else P(None)),
                  out_specs=P(None, None), axis_names={ax})
    return f(x_codes, w_codes, psum_exps)


def sharded_int_expert_gemm(mesh, inner, x_codes, w_codes, psum_exps, *,
                            gs: int, model_axis: str = "model",
                            wire: str = "int8"):
    """Expert-parallel stacked GEMM: [E, C, K] @ [E, K, N] over ``model``.

    Activations are replicated over the model axis, so "dispatch" is a
    free slice of each device's expert rows; the EP combine gathers the
    per-expert outputs as INT8 codes (each expert's static ``e_last``) —
    the int8 all-to-all equivalent.  W8A8 expert banks gather INT32.
    """
    d = _mesh_dim(mesh, model_axis)
    n_exp = int(x_codes.shape[0])
    if d <= 1 or n_exp % d:
        return inner.int_expert_gemm(x_codes, w_codes, psum_exps, gs=gs)
    ax = model_axis
    epd = n_exp // d
    per_col = psum_exps is not None and psum_exps.ndim == 3

    def body(xc, wc, exps):
        # exps arrives replicated [E, n_p(, N)]; slice our expert rows —
        # every device keeps all experts' e_last for the combine below
        if psum_exps is None:
            e_loc = None
        else:
            idx = jax.lax.axis_index(ax)
            e_loc = jax.lax.dynamic_slice_in_dim(exps, idx * epd, epd,
                                                 axis=0)
        y = inner.int_expert_gemm(xc, wc, e_loc, gs=gs)
        if psum_exps is None or wire == "fp32":
            return jax.lax.all_gather(y, ax, axis=0, tiled=True)
        e_last = e_loc[:, -1]                     # [E_loc] or [E_loc, N]
        eb = (e_last[:, None, :] if per_col else e_last[:, None, None])
        codes = jnp.right_shift(y, jnp.asarray(eb, jnp.int32))
        codes = jax.lax.all_gather(codes.astype(jnp.int8), ax,
                                   axis=0, tiled=True)
        ef = exps[:, -1]                          # full e_last: resident
        ebf = (ef[:, None, :] if per_col else ef[:, None, None])
        return jnp.left_shift(codes.astype(jnp.int32),
                              jnp.asarray(ebf, jnp.int32))

    e_spec = P() if psum_exps is None else P(*(None,) * psum_exps.ndim)
    e_arg = jnp.zeros((), jnp.int32) if psum_exps is None else psum_exps
    f = shard_map(body, mesh=mesh,
                  in_specs=(P(ax, None, None), P(ax, None, None), e_spec),
                  out_specs=P(None, None, None), axis_names={ax})
    return f(x_codes, w_codes, e_arg)


def sharded_kv_attention(mesh, inner, q, k_codes, v_codes, k_exp, v_exp,
                         length, *, model_axis: str = "model"):
    """Head-parallel paged attention: split Hq/Hkv over the model axis.

    Attention never mixes heads, so each device attends its own head
    slice against its slice of the INT8 pools — no collective at all;
    the (fp32) head gather happens downstream when the out-projection
    needs the full feature row, and is priced by the "attn" LayerPlans.
    The output stays logically full, physically head-sharded.
    """
    d = _mesh_dim(mesh, model_axis)
    hq = int(q.shape[-2])
    hkv = int(k_codes.shape[2])
    if d <= 1 or hq % d or hkv % d:
        return inner.kv_attention(q, k_codes, v_codes, k_exp, v_exp, length)
    ax = model_axis
    q_spec = (P(None, None, ax, None) if q.ndim == 4 else P(None, ax, None))

    def body(ql, kc, vc, ke, ve, ln):
        return inner.kv_attention(ql, kc, vc, ke, ve, ln)

    f = shard_map(body, mesh=mesh,
                  in_specs=(q_spec, P(None, None, ax, None),
                            P(None, None, ax, None), P(None, ax),
                            P(None, ax), P(None)),
                  out_specs=q_spec, axis_names={ax})
    return f(q, k_codes, v_codes, k_exp, v_exp, length)
