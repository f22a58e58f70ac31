"""Full-model assembly: decoder-only LMs, encoder-decoder, modality stubs.

An architecture is a repeating ``block_pattern`` of time-mix kinds
(attn | local | rwkv | rglru) with one channel mix (swiglu | gelu | moe |
rwkv_cm).  Full layers = ``n_units`` repeats of the pattern (stacked params,
``lax.scan`` over units, optional remat) + ``n_rem`` unstacked remainder
layers.  Every GEMM goes through ``dense`` so the paper's APSQ applies to
any architecture via ``cfg.quant``.

Three entry points per model:
  * ``forward``        — training / one-shot prefill; returns logits (and,
    when ``collect_cache`` is set, per-layer decode states for serving).
  * ``decode_step``    — one token with per-layer caches/recurrent states.
  * ``init_lm`` / ``lm_specs`` / ``init_decode_state`` — params, logical
    sharding specs (same tree), fresh decode state.

Modality stubs (assignment rule): ``[audio]``/``[vlm]`` archs take
precomputed frame/patch embeddings as inputs; there is no conv/ViT stack.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import QuantConfig
from .common import (
    Params,
    apply_norm,
    dense,
    init_embedding,
    init_linear,
    init_mlp,
    init_norm,
    apply_mlp,
    embedding_specs,
    linear_specs,
    mlp_specs,
    norm_specs,
)
from .attention import (
    attention_block,
    attention_specs,
    init_attention,
)
from .moe import init_moe, moe_ffn, moe_ffn_sharded, moe_specs
from .rwkv import (
    init_rwkv_channel_mix,
    init_rwkv_state,
    init_rwkv_time_mix,
    rwkv_channel_mix,
    rwkv_channel_mix_specs,
    rwkv_time_mix,
    rwkv_time_mix_specs,
)
from .rglru import (
    init_rglru_block,
    init_rglru_state,
    rglru_block,
    rglru_block_specs,
)
from .config import ModelConfig

SPEC_LEAF = lambda x: isinstance(x, tuple)  # logical-axis tuples are leaves


def tmap(f, *trees):
    """tree.map with logical-axis tuples treated as leaves."""
    return jax.tree.map(f, *trees, is_leaf=SPEC_LEAF)


# ---------------------------------------------------------------------------
# One layer (time mix + channel mix, pre-norm residual)
# ---------------------------------------------------------------------------

def _init_ffn(key, cfg: ModelConfig, quant, name: str = ""):
    if cfg.mlp == "moe":
        return init_moe(key, cfg.d_model, cfg.d_ff, cfg.n_experts,
                        cfg.top_k, cfg.jdtype, quant=quant, name=name)
    if cfg.mlp == "rwkv_cm":
        return init_rwkv_channel_mix(key, cfg.d_model, cfg.d_ff, cfg.jdtype,
                                     quant=quant, name=name)
    return init_mlp(key, cfg.d_model, cfg.d_ff, cfg.jdtype, kind=cfg.mlp,
                    quant=quant, name=name)


def _ffn_specs(cfg: ModelConfig, quant, name: str = ""):
    if cfg.mlp == "moe":
        return moe_specs(quant, name)
    if cfg.mlp == "rwkv_cm":
        return rwkv_channel_mix_specs(quant, name)
    return mlp_specs(cfg.mlp, quant, name)


def init_layer(key, cfg: ModelConfig, kind: str, cross: bool = False,
               name: str = "unit.0") -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    quant = cfg.policy
    p: Params = {"ln1": init_norm(cfg.d_model, cfg.jdtype, cfg.norm),
                 "ln2": init_norm(cfg.d_model, cfg.jdtype, cfg.norm)}
    if kind in ("attn", "local"):
        p["mix"] = init_attention(k1, cfg.d_model, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.hd, cfg.jdtype,
                                  quant=quant, name=f"{name}.mix")
    elif kind == "rwkv":
        p["mix"] = init_rwkv_time_mix(k1, cfg.d_model, cfg.n_heads, cfg.hd,
                                      cfg.jdtype, quant=quant,
                                      name=f"{name}.mix")
    elif kind == "rglru":
        p["mix"] = init_rglru_block(k1, cfg.d_model, cfg.d_rnn, cfg.jdtype,
                                    quant=quant, name=f"{name}.mix")
    else:
        raise ValueError(kind)
    p["ffn"] = _init_ffn(k2, cfg, quant, name=f"{name}.ffn")
    if cross:
        p["lnx"] = init_norm(cfg.d_model, cfg.jdtype, cfg.norm)
        p["xattn"] = init_attention(k3, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.hd, cfg.jdtype,
                                    quant=quant, name=f"{name}.xattn")
    return p


def layer_specs(cfg: ModelConfig, kind: str, cross: bool = False,
                name: str = "unit.0") -> Params:
    quant = cfg.policy
    s: Params = {"ln1": norm_specs(cfg.norm), "ln2": norm_specs(cfg.norm)}
    if kind in ("attn", "local"):
        s["mix"] = attention_specs(quant, f"{name}.mix")
    elif kind == "rwkv":
        s["mix"] = rwkv_time_mix_specs(quant, f"{name}.mix")
    elif kind == "rglru":
        s["mix"] = rglru_block_specs(quant, f"{name}.mix")
    s["ffn"] = _ffn_specs(cfg, quant, name=f"{name}.ffn")
    if cross:
        s["lnx"] = norm_specs(cfg.norm)
        s["xattn"] = attention_specs(quant, f"{name}.xattn")
    return s


def init_layer_state(cfg: ModelConfig, kind: str, batch: int,
                     cache_len: int) -> Params:
    """Fresh decode state for one layer of the given kind."""
    if kind == "attn":
        shape = (batch, cache_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, cfg.jdtype),
                "v": jnp.zeros(shape, cfg.jdtype)}
    if kind == "local":
        shape = (batch, min(cfg.local_window, cache_len),
                 cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, cfg.jdtype),
                "v": jnp.zeros(shape, cfg.jdtype)}
    if kind == "rwkv":
        return init_rwkv_state(batch, cfg.d_model, cfg.n_heads, cfg.hd,
                               dtype=cfg.jdtype)
    if kind == "rglru":
        return {"rec": init_rglru_state(batch, cfg.d_rnn, dtype=cfg.jdtype)}
    raise ValueError(kind)


def apply_layer(
    p: Params,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    kind: str,
    mesh=None,
    state: Params | None = None,
    pos: jax.Array | int = 0,
    enc_out: jax.Array | None = None,
    causal: bool = True,
    tap: list | None = None,
    backend=None,
    page_table=None,
):
    """One pre-norm block.  ``state`` not None => decode (single token).

    Returns (x, new_state); new_state is None when training without cache.
    ``tap`` is the calibration capture list, threaded down to every
    quantized linear (``repro.core.TapRecord`` per eager invocation).
    ``backend`` selects the integer execution backend (``repro.exec``)
    for deployed params and reaches every projection GEMM in the block.
    ``page_table`` ([B, n_max] physical page ids) switches attention
    layers whose state is a paged INT8 KV cache onto the paged decode
    path (``pos`` is then a per-slot [B] vector).
    """
    # (§Perf it4, refuted: an explicit seq-shard constraint on the
    # residual stream added reshards — GSPMD already propagates SP from
    # the ddlerp/rglru hints.  Left unconstrained.)
    quant = cfg.quant if cfg.quant.enabled else None
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm)
        new_state: Params = {}

        if kind in ("attn", "local"):
            window = cfg.local_window if kind == "local" else None
            if state is not None and "k_pages" in state:
                cache = state  # paged INT8 pools + running exponents
            elif state is not None:
                cache = {"k": state["k"], "v": state["v"]}
            else:
                cache = None
            out, kv = attention_block(
                p["mix"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, rope_fraction=cfg.rope_fraction,
                rope_theta=cfg.rope_theta, causal=causal, window=window,
                softcap=cfg.softcap, quant=quant, cache=cache, pos=pos,
                mesh=mesh, tap=tap, backend=backend, page_table=page_table)
            new_state = kv
        elif kind == "rwkv":
            # Chunked paged prefill must stay bit-identical to the per-token
            # scan; the chunk-parallel WKV reorders fp32 accumulation, so
            # force the sequential impl whenever a multi-token chunk runs
            # against paged serving state.
            exact = page_table is not None and x.shape[1] > 1
            out, tm_state = rwkv_time_mix(
                p["mix"], h, n_heads=cfg.n_heads, head_dim=cfg.hd, quant=quant,
                impl="scan" if exact else cfg.wkv_impl,
                wkv_chunk=cfg.wkv_chunk, mesh=mesh,
                state=state["tm"] if state is not None else None, tap=tap,
                backend=backend)
            new_state = {"tm": tm_state}
        elif kind == "rglru":
            out, rec_state = rglru_block(
                p["mix"], h, quant=quant, mesh=mesh,
                state=state["rec"] if state is not None else None, tap=tap,
                backend=backend,
                exact_scan=page_table is not None and x.shape[1] > 1)
            new_state = {"rec": rec_state}
        else:
            raise ValueError(kind)
        x = x + out

        if "xattn" in p and enc_out is not None:
            hx = apply_norm(p["lnx"], x, cfg.norm)
            outx, _ = attention_block(
                p["xattn"], hx, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, quant=quant, xkv=enc_out, use_rope=False,
                mesh=mesh, tap=tap, backend=backend)
            x = x + outx

    with jax.named_scope("mlp"):
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if cfg.mlp == "moe":
            from repro.core import DeployedQuantState
            deployed_moe = isinstance(p["ffn"].get("qp_wi"),
                                      DeployedQuantState)
            if mesh is not None and not deployed_moe:
                y = moe_ffn_sharded(p["ffn"], h2, mesh=mesh,
                                    n_experts=cfg.n_experts, top_k=cfg.top_k,
                                    capacity_factor=cfg.capacity_factor,
                                    quant=quant, backend=backend)
            else:
                # Deployed expert banks: EP lives INSIDE the backend
                # (``ShardedBackend.int_expert_gemm`` shard_maps the expert
                # axis and gathers outputs as INT8 codes), so the pure path
                # is the right wrapper — ``moe_ffn_sharded``'s fp32 psum
                # combine would both double-wrap shard_map and lose the
                # int8-on-the-wire saving.
                y = moe_ffn(p["ffn"], h2, n_experts=cfg.n_experts,
                            top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor,
                            quant=quant, tap=tap, backend=backend)
        elif cfg.mlp == "rwkv_cm":
            y, cm_state = rwkv_channel_mix(
                p["ffn"], h2, quant=quant, mesh=mesh,
                state=state["cm"] if (state is not None and "cm" in state)
                else None, tap=tap, backend=backend)
            if state is not None:
                new_state["cm"] = cm_state
        else:
            y = apply_mlp(p["ffn"], h2, kind=cfg.mlp, quant=quant, tap=tap,
                          backend=backend)
        x = x + y
    # RWKV layers always carry channel-mix shift state in decode.
    if kind == "rwkv" and state is not None and "cm" not in new_state:
        new_state["cm"] = {"shift": h2[:, -1:]}
    return x, new_state


# ---------------------------------------------------------------------------
# Units (one repeat of block_pattern) — scan-over-units with stacked params
# ---------------------------------------------------------------------------

def init_unit(key, cfg: ModelConfig, cross: bool = False,
              name: str = "unit") -> Params:
    keys = jax.random.split(key, len(cfg.block_pattern))
    return {str(i): init_layer(k, cfg, kind, cross=cross, name=f"{name}.{i}")
            for i, (k, kind) in enumerate(zip(keys, cfg.block_pattern))}


def unit_specs(cfg: ModelConfig, cross: bool = False,
               name: str = "unit") -> Params:
    return {str(i): layer_specs(cfg, kind, cross=cross, name=f"{name}.{i}")
            for i, kind in enumerate(cfg.block_pattern)}


def init_unit_state(cfg: ModelConfig, batch: int, cache_len: int) -> Params:
    return {str(i): init_layer_state(cfg, kind, batch, cache_len)
            for i, kind in enumerate(cfg.block_pattern)}


def apply_unit(p: Params, x, *, cfg: ModelConfig, mesh=None, state=None,
               pos=0, enc_out=None, causal=True, tap: list | None = None,
               backend=None, page_table=None):
    new_state = {}
    for i, kind in enumerate(cfg.block_pattern):
        x, s = apply_layer(
            p[str(i)], x, cfg=cfg, kind=kind, mesh=mesh,
            state=state[str(i)] if state is not None else None,
            pos=pos, enc_out=enc_out, causal=causal, tap=tap,
            backend=backend, page_table=page_table)
        new_state[str(i)] = s
    return x, new_state


def _stack_init(key, n: int, init_fn):
    """vmap an init function over ``n`` split keys -> stacked params."""
    if n == 0:
        return None
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def stack_specs(spec_tree: Params) -> Params:
    """Prepend the 'layers' logical axis to every leaf (scan-stacked)."""
    return tmap(lambda t: ("layers",) + tuple(t), spec_tree)


# ---------------------------------------------------------------------------
# Full LM
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> Params:
    cfg.validate()
    ks = jax.random.split(key, 8)
    p: Params = {"embed": init_embedding(ks[0], cfg.vocab, cfg.d_model,
                                         cfg.jdtype)}
    cross = cfg.encdec
    if cfg.scan_layers:
        p["units"] = _stack_init(ks[1], cfg.n_units,
                                 lambda k: init_unit(k, cfg, cross=cross))
    else:  # unstacked: python-unrolled units (tiny models, eager passes)
        uk = jax.random.split(ks[1], max(cfg.n_units, 1))
        p["units"] = {f"u{i}": init_unit(uk[i], cfg, cross=cross)
                      for i in range(cfg.n_units)}
    if cfg.n_rem:
        rk = jax.random.split(ks[2], cfg.n_rem)
        p["rem"] = {str(i): init_layer(rk[i], cfg, cfg.block_pattern[i],
                                       cross=cross, name=f"rem.{i}")
                    for i in range(cfg.n_rem)}
    p["final_norm"] = init_norm(cfg.d_model, cfg.jdtype, cfg.norm)
    if not cfg.tie_embeddings:
        p["head"] = init_linear(ks[3], (cfg.d_model, cfg.vocab), cfg.jdtype)
    if cfg.encdec:
        enc_cfg = dataclasses.replace(cfg, encdec=False)
        p["encoder"] = {
            "units": _stack_init(
                ks[4], cfg.n_enc_layers // len(cfg.block_pattern),
                lambda k: init_unit(k, enc_cfg, name="encoder.unit")),
            "final_norm": init_norm(cfg.d_model, cfg.jdtype, cfg.norm),
        }
    if cfg.frontend == "vision":
        # Stub projection from provided patch embeddings to d_model.
        p["frontend_proj"] = init_linear(ks[5], (cfg.d_model, cfg.d_model),
                                         cfg.jdtype)
    return p


def lm_specs(cfg: ModelConfig) -> Params:
    s: Params = {"embed": embedding_specs()}
    cross = cfg.encdec
    if cfg.scan_layers:
        s["units"] = stack_specs(unit_specs(cfg, cross=cross))
    else:
        s["units"] = {f"u{i}": unit_specs(cfg, cross=cross)
                      for i in range(cfg.n_units)}
    if cfg.n_rem:
        s["rem"] = {str(i): layer_specs(cfg, cfg.block_pattern[i],
                                        cross=cross, name=f"rem.{i}")
                    for i in range(cfg.n_rem)}
    s["final_norm"] = norm_specs(cfg.norm)
    if not cfg.tie_embeddings:
        s["head"] = linear_specs(("embed", "vocab"))
    if cfg.encdec:
        s["encoder"] = {"units": stack_specs(unit_specs(
                            cfg, name="encoder.unit")),
                        "final_norm": norm_specs(cfg.norm)}
    if cfg.frontend == "vision":
        s["frontend_proj"] = linear_specs(("embed", "embed_out"))
    return s


def _remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _scan_units(params_units, x, *, cfg: ModelConfig, mesh, pos, enc_out,
                causal, tap: list | None = None, backend=None):
    if params_units is None:
        return x

    if not cfg.scan_layers:  # unstacked dict (tiny models, eager passes)
        for i in range(len(params_units)):
            x, _ = apply_unit(params_units[f"u{i}"], x, cfg=cfg, mesh=mesh,
                              pos=pos, enc_out=enc_out, causal=causal,
                              tap=tap, backend=backend)
        return x

    # The scan body traces, so the capture tap cannot see its linears —
    # ``repro.quant.calibrate_model`` slices the stacked params and runs
    # per-unit eager passes instead.
    def body(carry, unit_p):
        y, _ = apply_unit(unit_p, carry, cfg=cfg, mesh=mesh, pos=pos,
                          enc_out=enc_out, causal=causal, backend=backend)
        return y, ()

    body = _remat(body, cfg)
    x, _ = jax.lax.scan(body, x, params_units)
    return x


def embed_inputs(p: Params, cfg: ModelConfig, tokens: jax.Array | None,
                 embeds: jax.Array | None = None) -> jax.Array:
    """Token embedding + optional modality-stub embeddings.

    vision: ``embeds`` [B, n_img, d] are projected and prepended.
    audio (encdec): encoder consumes ``embeds`` directly; decoder uses
    ``tokens`` only — handled by ``forward``.
    """
    parts = []
    if embeds is not None and cfg.frontend == "vision":
        fe = dense(p["frontend_proj"], embeds.astype(cfg.jdtype), None)
        parts.append(fe)
    if tokens is not None:
        parts.append(jnp.take(p["embed"]["table"], tokens, axis=0))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return x


def encode(p: Params, cfg: ModelConfig, enc_embeds: jax.Array,
           mesh=None, backend=None) -> jax.Array:
    """Encoder stack over precomputed frame embeddings (audio stub)."""
    x = enc_embeds.astype(cfg.jdtype)
    enc_cfg = dataclasses.replace(cfg, encdec=False, scan_layers=True)
    x = _scan_units(p["encoder"]["units"], x, cfg=enc_cfg, mesh=mesh, pos=0,
                    enc_out=None, causal=False, backend=backend)
    return apply_norm(p["encoder"]["final_norm"], x, cfg.norm)


def logits_from_hidden(p: Params, cfg: ModelConfig, x: jax.Array,
                       mesh=None, backend=None):
    from repro.core import (DeployedQuantState, QuantState, quant_dense,
                            tied_head_weight)
    from .common import act_spec, shard_hint
    with jax.named_scope("lm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            # The tied head GEMM is quantizable like any projection: a
            # ``qp_head`` state appears after ``calibrate_model``
            # (fake-quant QAT view over ``tied_head_weight(table)``) and
            # ``export_quantized`` deploys it as INT8 codes + shift
            # exponents routed through the exec backend.
            qp_head = p["embed"].get("qp_head")
            if isinstance(qp_head, DeployedQuantState):
                logits = quant_dense(x, None, qp_head, backend=backend)
            elif isinstance(qp_head, QuantState):
                logits = quant_dense(
                    x, tied_head_weight(p["embed"]["table"]), qp_head)
            else:
                logits = jnp.einsum("bsd,vd->bsv", x, p["embed"]["table"])
        else:
            logits = dense(p["head"], x, None, backend=backend)
    return shard_hint(logits, act_spec(mesh, x.shape[0], feat=cfg.vocab))


def forward(
    p: Params,
    cfg: ModelConfig,
    tokens: jax.Array | None,
    *,
    embeds: jax.Array | None = None,
    enc_embeds: jax.Array | None = None,
    mesh=None,
    pos: jax.Array | int = 0,
    tap: list | None = None,
    backend=None,
) -> jax.Array:
    """Training / one-shot prefill forward; returns logits [B, S_out, V].

    ``embeds``     — vision patch embeddings (prepended to tokens).
    ``enc_embeds`` — audio frame embeddings for the encoder (encdec only).
    ``tap``        — calibration capture list (reaches every linear only
    when ``cfg.scan_layers`` is False; ``calibrate_model`` handles the
    scanned case by per-unit eager passes).
    ``backend``    — integer execution backend for deployed params
    (``repro.exec``: "oracle" | "pallas" | "auto").
    """
    enc_out = None
    if cfg.encdec:
        assert enc_embeds is not None, "enc-dec model needs enc_embeds"
        enc_out = encode(p, cfg, enc_embeds, mesh=mesh, backend=backend)
    x = embed_inputs(p, cfg, tokens, embeds)
    x = _scan_units(p["units"], x, cfg=cfg, mesh=mesh, pos=pos,
                    enc_out=enc_out, causal=True, tap=tap, backend=backend)
    for i in range(cfg.n_rem):
        x, _ = apply_layer(p["rem"][str(i)], x, cfg=cfg,
                           kind=cfg.block_pattern[i], mesh=mesh, pos=pos,
                           enc_out=enc_out, tap=tap, backend=backend)
    return logits_from_hidden(p, cfg, x, mesh, backend=backend)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int) -> Params:
    """Stacked (per-unit) + remainder decode state for the whole model."""
    state: Params = {}
    if cfg.n_units:
        unit_state = init_unit_state(cfg, batch, cache_len)
        if cfg.scan_layers:
            state["units"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_units,) + a.shape),
                unit_state)
        else:
            state["units"] = {
                f"u{i}": init_unit_state(cfg, batch, cache_len)
                for i in range(cfg.n_units)}
    for i in range(cfg.n_rem):
        state[f"rem{i}"] = init_layer_state(cfg, cfg.block_pattern[i], batch,
                                            cache_len)
    return state


def decode_state_specs(cfg: ModelConfig) -> Params:
    """Logical axes for the decode state (cache sharding)."""
    def kv_spec():
        return {"k": ("batch", None, "kvheads_cache", None),
                "v": ("batch", None, "kvheads_cache", None)}

    def layer_state_spec(kind):
        if kind in ("attn", "local"):
            return kv_spec()
        if kind == "rwkv":
            return {"tm": {"shift": ("batch", None, None),
                           "wkv": ("batch", "heads", None, None)},
                    "cm": {"shift": ("batch", None, None)}}
        if kind == "rglru":
            return {"rec": {"h": ("batch", "rnn"),
                            "conv": ("batch", None, "rnn")}}
        raise ValueError(kind)

    state: Params = {}
    if cfg.n_units:
        unit = {str(i): layer_state_spec(k)
                for i, k in enumerate(cfg.block_pattern)}
        state["units"] = stack_specs(unit)
    for i in range(cfg.n_rem):
        state[f"rem{i}"] = layer_state_spec(cfg.block_pattern[i])
    return state


def decode_step(
    p: Params,
    cfg: ModelConfig,
    state: Params,
    token: jax.Array,
    pos: jax.Array,
    *,
    enc_out: jax.Array | None = None,
    mesh=None,
    backend=None,
):
    """One decode step.  token: [B, 1] int32; pos: scalar int32 (position of
    this token).  Returns (logits [B, 1, V], new_state).  ``backend``
    selects the integer execution backend for deployed params — the
    decode hot loop runs the Pallas kernel when it resolves to "pallas"."""
    x = jnp.take(p["embed"]["table"], token, axis=0)

    new_state = dict(state)
    if cfg.n_units:
        if cfg.scan_layers:
            def body(carry, xs):
                unit_p, unit_s = xs
                y, s = apply_unit(unit_p, carry, cfg=cfg, mesh=mesh,
                                  state=unit_s, pos=pos, enc_out=enc_out,
                                  backend=backend)
                return y, s

            x, new_units = jax.lax.scan(body, x, (p["units"], state["units"]))
            new_state["units"] = new_units
        else:
            new_units = {}
            for i in range(cfg.n_units):
                x, s = apply_unit(p["units"][f"u{i}"], x, cfg=cfg, mesh=mesh,
                                  state=state["units"][f"u{i}"], pos=pos,
                                  enc_out=enc_out, backend=backend)
                new_units[f"u{i}"] = s
            new_state["units"] = new_units
    for i in range(cfg.n_rem):
        x, s = apply_layer(p["rem"][str(i)], x, cfg=cfg,
                           kind=cfg.block_pattern[i], mesh=mesh,
                           state=state[f"rem{i}"], pos=pos, enc_out=enc_out,
                           backend=backend)
        new_state[f"rem{i}"] = s
    logits = logits_from_hidden(p, cfg, x, mesh, backend=backend)
    return logits, new_state


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching serving: INT8 pools + page table)
# ---------------------------------------------------------------------------

def init_paged_layer_state(cfg: ModelConfig, kind: str, batch: int,
                           page_size: int, n_pages: int) -> Params:
    """Fresh paged decode state for one layer.

    Attention layers get shared INT8 page pools plus per-(slot, kv-head)
    running PO2 exponents (``repro.serving.paged_cache``); recurrent kinds
    keep their position-free per-slot states.  "local" (ring-buffer)
    layers are not paged yet.
    """
    if kind == "attn":
        from repro.serving.paged_cache import EXP_FLOOR
        shape = (n_pages, page_size, cfg.n_kv_heads, cfg.hd)
        return {"k_pages": jnp.zeros(shape, jnp.int8),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "k_exp": jnp.full((batch, cfg.n_kv_heads), EXP_FLOOR,
                                  jnp.int32),
                "v_exp": jnp.full((batch, cfg.n_kv_heads), EXP_FLOOR,
                                  jnp.int32)}
    if kind == "local":
        raise NotImplementedError(
            "paged serving does not cover local-attention layers yet")
    return init_layer_state(cfg, kind, batch, cache_len=1)


def init_paged_decode_state(cfg: ModelConfig, batch: int, *, page_size: int,
                            n_pages: int) -> Params:
    """Paged analogue of ``init_decode_state`` (same tree structure)."""
    def unit_state():
        return {str(i): init_paged_layer_state(cfg, kind, batch, page_size,
                                               n_pages)
                for i, kind in enumerate(cfg.block_pattern)}

    state: Params = {}
    if cfg.n_units:
        if cfg.scan_layers:
            state["units"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_units,) + a.shape),
                unit_state())
        else:
            state["units"] = {f"u{i}": unit_state()
                              for i in range(cfg.n_units)}
    for i in range(cfg.n_rem):
        state[f"rem{i}"] = init_paged_layer_state(
            cfg, cfg.block_pattern[i], batch, page_size, n_pages)
    return state


def forward_paged_chunk(
    p: Params,
    cfg: ModelConfig,
    state: Params,
    tokens: jax.Array,
    pos: jax.Array,
    page_table: jax.Array,
    *,
    mesh=None,
    backend=None,
):
    """One prefill chunk (or decode step, C=1) over the paged INT8 cache.

    ``tokens`` [B, C] is a block of C consecutive prompt tokens whose
    first token sits at per-slot position ``pos`` [B]; ``page_table``
    [B, n_max] maps each slot's logical pages to physical pool pages.
    All non-attention GEMMs run once at m=C; attention layers write the
    chunk's quantized KV through the same per-token bump-rescale
    recurrence as decode (the paged pools end bit-identical to C
    single-token calls) and attend with an in-chunk causal mask.
    Recurrent layers run exact sequential scans (``apply_layer`` forces
    rwkv impl="scan" / rglru exact_scan when C > 1), so the carried
    states match the token-by-token path bit-for-bit too.

    Returns (logits [B, 1, V] for the LAST chunk row, new_state)."""
    with jax.named_scope("prefill"):
        return _forward_paged(p, cfg, state, tokens, pos, page_table,
                              mesh=mesh, backend=backend)


def _forward_paged(p, cfg, state, tokens, pos, page_table, *, mesh,
                   backend):
    """The forward shared by ``forward_paged_chunk`` (named ``prefill``
    in the compiled program's op metadata) and ``decode_step_paged``."""
    x = jnp.take(p["embed"]["table"], tokens, axis=0)

    new_state = dict(state)
    if cfg.n_units:
        if cfg.scan_layers:
            def body(carry, xs):
                unit_p, unit_s = xs
                y, s = apply_unit(unit_p, carry, cfg=cfg, mesh=mesh,
                                  state=unit_s, pos=pos, backend=backend,
                                  page_table=page_table)
                return y, s

            x, new_units = jax.lax.scan(body, x, (p["units"], state["units"]))
            new_state["units"] = new_units
        else:
            new_units = {}
            for i in range(cfg.n_units):
                x, s = apply_unit(p["units"][f"u{i}"], x, cfg=cfg, mesh=mesh,
                                  state=state["units"][f"u{i}"], pos=pos,
                                  backend=backend, page_table=page_table)
                new_units[f"u{i}"] = s
            new_state["units"] = new_units
    for i in range(cfg.n_rem):
        x, s = apply_layer(p["rem"][str(i)], x, cfg=cfg,
                           kind=cfg.block_pattern[i], mesh=mesh,
                           state=state[f"rem{i}"], pos=pos,
                           backend=backend, page_table=page_table)
        new_state[f"rem{i}"] = s
    logits = logits_from_hidden(p, cfg, x[:, -1:], mesh, backend=backend)
    return logits, new_state


def decode_step_paged(
    p: Params,
    cfg: ModelConfig,
    state: Params,
    token: jax.Array,
    pos: jax.Array,
    page_table: jax.Array,
    *,
    mesh=None,
    backend=None,
):
    """One decode step over the paged INT8 KV cache.

    Unlike ``decode_step``, ``pos`` is a per-slot [B] int32 vector (slots
    advance independently under continuous batching) and ``page_table``
    [B, n_max] maps each slot's logical pages to physical pool pages.
    Returns (logits [B, 1, V], new_state).  This is exactly
    ``forward_paged_chunk`` with a chunk of one token."""
    return _forward_paged(p, cfg, state, token, pos, page_table,
                          mesh=mesh, backend=backend)


# ---------------------------------------------------------------------------
# State-tree slot axes (shared by the serving engines and the fused decode)
# ---------------------------------------------------------------------------

def batch_state_axes(state: Params, scan_layers: bool = True) -> Params:
    """Per-leaf slot axis of a dense decode state: stacked unit states are
    [n_units, B, ...] -> 1; unstacked / remainder states are [B, ...] -> 0."""
    def f(path, a):
        names = [str(getattr(p, "key", "")) for p in path]
        return 1 if (scan_layers and "units" in names) else 0
    return jax.tree_util.tree_map_with_path(f, state)


def paged_state_axes(state: Params, scan_layers: bool = True) -> Params:
    """Per-leaf slot axis of a paged decode state.

    Page pools (``k_pages``/``v_pages``) are shared by every slot and get
    the sentinel -1 (pass whole / take whole); per-slot leaves (running
    exponents, recurrent states) get their slot axis as in
    ``batch_state_axes``."""
    def f(path, a):
        names = [str(getattr(p, "key", "")) for p in path]
        if names and names[-1] in ("k_pages", "v_pages"):
            return -1
        return 1 if (scan_layers and "units" in names) else 0
    return jax.tree_util.tree_map_with_path(f, state)


def _keep_slots(old, new, ax: int, on: jax.Array):
    """Revert a state leaf to ``old`` for slots where ``on`` is False.
    ``ax`` is the leaf's slot axis (-1: shared pool leaf, always new)."""
    if ax == -1:
        return new
    m = on.reshape((1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
    return jnp.where(m, new, old)


def decode_horizon_paged(
    p: Params,
    cfg: ModelConfig,
    state: Params,
    tokens: jax.Array,
    pos: jax.Array,
    page_table: jax.Array,
    *,
    horizon: int,
    active: jax.Array,
    budget: jax.Array,
    remaining: jax.Array,
    eos: jax.Array,
    greedy: bool = True,
    temperature: float = 1.0,
    rng: jax.Array | None = None,
    mesh=None,
    backend=None,
):
    """Fused multi-step decode: ``horizon`` iterations of
    ``decode_step_paged`` inside ONE ``lax.scan``, with sampling, per-slot
    EOS / token-budget detection, position advance and paged-KV writes all
    on device — the serving engine syncs with the host once per macro-step
    instead of once per token.

    ``tokens`` [B, 1] are each slot's last generated tokens (0 for masked
    slots); ``pos`` [B] the positions they will be written at;
    ``page_table`` [B, n_max] the PRE-BUILT physical page map covering
    every position the scan can reach (the engine's ``_ensure_capacity``
    reserves [pos, pos + budget) up front).  Per-slot int32/bool vectors:

      * ``active``    — False: empty or mid-prefill slot; rides the batch
        inert for the whole horizon (null-page writes, leaves reverted).
      * ``budget``    — device steps the slot may take this macro-step
        (<= horizon; the engine shrinks it when the page pool is tight).
      * ``remaining`` — tokens left before ``max_new_tokens``.
      * ``eos``       — per-slot stop token id, -1 when none.

    Step ``t`` masks a slot exactly the way the engine's single-step path
    masks non-decoding slots — zeroed table row (writes land on the null
    page), per-slot leaves reverted, fed token 0 at position 0 (so its
    attention reads one cache tile, not its stale length), position
    frozen in the carry — so
    ``horizon`` fused steps are token- AND KV-bit-identical to ``horizon``
    single ``decode_step_paged`` calls with host-side masking, including a
    slot that hits EOS or exhausts its token budget mid-horizon.
    Recurrent (rwkv/rglru) per-step states ride the scan carry like every
    other per-slot leaf.

    Returns ``(tok_block [B, horizon], emitted [B, horizon] bool,
    new_state, new_pos, new_rng)``; ``emitted[s]`` is a prefix mask — the
    host appends ``tok_block[s, t]`` for every True ``emitted[s, t]``."""
    from repro.serving.paged_cache import NULL_PAGE
    axes = paged_state_axes(state, cfg.scan_layers)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    temp = jnp.maximum(temperature, 1e-6)

    def body(carry, _):
        st, tok, ps, act, bud, rem, key = carry
        on = act & (bud > 0)
        tbl = jnp.where(on[:, None], page_table, NULL_PAGE)
        lg, st2 = decode_step_paged(p, cfg, st, tok,
                                    jnp.where(on, ps, 0), tbl,
                                    mesh=mesh, backend=backend)
        st2 = jax.tree.map(lambda o, n, ax: _keep_slots(o, n, ax, on),
                           st, st2, axes)
        with jax.named_scope("sample"):
            logits = lg[:, -1] / temp
            key, sub = jax.random.split(key)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(sub, logits,
                                             axis=-1).astype(jnp.int32)
            rem2 = jnp.where(on, rem - 1, rem)
            fin = on & ((nxt == eos) | (rem2 <= 0))
            tok2 = jnp.where(on, jnp.where(fin, 0, nxt), tok[:, 0])[:, None]
        carry2 = (st2, tok2, ps + on.astype(ps.dtype), act & ~fin,
                  bud - on.astype(bud.dtype), rem2, key)
        return carry2, (nxt, on)

    carry = (state, tokens, jnp.asarray(pos, jnp.int32), active,
             jnp.asarray(budget, jnp.int32), jnp.asarray(remaining,
                                                         jnp.int32), rng)
    with jax.named_scope("decode"):
        (st, _, ps, _, _, _, key), (toks, ons) = jax.lax.scan(
            body, carry, None, length=horizon)
    return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(ons, 0, 1), st, ps, key)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: jax.Array | None = None, z_loss: float = 0.0):
    """Token-mean cross entropy in fp32 (+ optional z-loss), vocab-shard safe.

    logits: [B, S, V]; labels: [B, S] int32; mask: [B, S] (1 = contributes).
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
