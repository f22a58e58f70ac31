"""Execution-backend layer: registry, execute_gemm parity, kernel serving.

The acceptance bar for the backend subsystem: every deployed projection
GEMM — dense linears (per-channel scales), MoE expert banks, the
tied-embedding head — dispatches through ``repro.exec.execute_gemm``, and
``ServingEngine.from_exported`` with the Pallas backend greedy-decodes
token-for-token identically to ``backend="oracle"``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeployedQuantState, QuantConfig, quant_params_init, \
    calibrate_dense
from repro.exec import (
    AutoBackend,
    ExecBackend,
    PallasBackend,
    available_backends,
    execute_expert_gemm,
    execute_gemm,
    get_backend,
    register_backend,
)
from repro.models.config import ModelConfig
from repro.models.model import forward, init_lm
from repro.quant import QuantPolicy, calibrate_model, export_quantized, \
    snap_params_po2

# The Pallas kernels in the interpreter: the CPU form of ``pallas``.
PALLAS = PallasBackend(interpret=True)


def _cfg(**kw):
    base = dict(name="ex", family="dense", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, d_ff=64, vocab=64, dtype="float32",
                scan_layers=False, quant=QuantConfig.apsq(gs=2, n_p=4))
    base.update(kw)
    return ModelConfig(**base)


def _exported_linear(key, m=8, k=32, n=16, per_channel=True,
                     psum=QuantConfig.apsq(gs=2, n_p=4).psum):
    cfg = QuantConfig(enabled=True, per_channel_w=per_channel, psum=psum)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.1
    qp = calibrate_dense(quant_params_init(w, cfg, name="lin"), x, w)
    dep, _ = export_quantized({"lin": {"w": w, "qp": qp}})
    return x, dep["lin"]["qp"]


# ------------------------------ registry -----------------------------------

def test_registry_and_resolution():
    assert {"auto", "oracle", "pallas"} <= set(available_backends())
    assert get_backend("oracle").name == "oracle"
    assert get_backend(None).name == "auto"
    inst = PallasBackend(interpret=True)
    assert get_backend(inst) is inst  # instances pass through
    with pytest.raises(KeyError, match="unknown exec backend"):
        get_backend("does-not-exist")
    # auto resolves to a leaf backend (oracle on CPU CI)
    leaf = AutoBackend().resolve()
    assert leaf.name in ("oracle", "pallas")
    # custom registration
    class Custom(ExecBackend):
        name = "custom-test"
        def int_gemm(self, x_codes, w_codes, psum_exps, *, gs):
            return get_backend("oracle").int_gemm(
                x_codes, w_codes, psum_exps, gs=gs)
    register_backend("custom-test", Custom())
    assert get_backend("custom-test").name == "custom-test"


# ------------------------------ execute_gemm -------------------------------

@pytest.mark.parametrize("per_channel", [False, True])
def test_execute_gemm_backend_parity(per_channel):
    """oracle == pallas (interpret) on exported layers, both exponent
    layouts ([n_p] per-tensor and [n_p, N] per-channel)."""
    x, dq = _exported_linear(jax.random.PRNGKey(0), per_channel=per_channel)
    assert dq.psum_exps.ndim == (2 if per_channel else 1)
    y_o = execute_gemm(dq, x, backend="oracle")
    y_p = execute_gemm(dq, x, backend=PallasBackend(interpret=True))
    np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_p))


def test_execute_gemm_flattens_leading_dims():
    """[B, T, K] activations flatten to one [M, K] GEMM; decode's
    [B, 1, K] shape (M = B) works on both backends."""
    x, dq = _exported_linear(jax.random.PRNGKey(1))
    for shape in ((2, 4, 32), (3, 1, 32)):
        xb = jnp.broadcast_to(x[0], shape)
        y_o = execute_gemm(dq, xb, backend="oracle")
        y_p = execute_gemm(dq, xb, backend=PALLAS)
        assert y_o.shape == shape[:-1] + dq.out_dims
        np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_p))


def test_execute_gemm_w8a8_baseline_path():
    """psum_exps=None (plain W8A8 export) runs the baseline integer GEMM
    on both backends."""
    x, dq = _exported_linear(
        jax.random.PRNGKey(2),
        psum=QuantConfig.w8a8().psum)
    assert dq.psum_exps is None
    y_o = execute_gemm(dq, x, backend="oracle")
    y_p = execute_gemm(dq, x, backend=PALLAS)
    np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_p))


def test_execute_gemm_under_jit_and_vmap():
    x, dq = _exported_linear(jax.random.PRNGKey(3))
    f = jax.jit(lambda a: execute_gemm(dq, a, backend=PALLAS))
    np.testing.assert_array_equal(
        np.asarray(f(x)), np.asarray(execute_gemm(dq, x, backend="oracle")))
    xb = jnp.stack([x, x * 0.5])
    yb = jax.vmap(lambda a: execute_gemm(dq, a, backend=PALLAS))(xb)
    np.testing.assert_array_equal(
        np.asarray(yb[0]), np.asarray(execute_gemm(dq, x, backend="oracle")))


# ------------------------------ MoE expert banks ---------------------------

def test_moe_expert_bank_export_and_parity():
    """Expert tensors export to stacked DeployedQuantState (per-expert
    codes + exponent banks); execute_expert_gemm matches per-expert
    execute_gemm on both backends."""
    cfg = _cfg(mlp="moe", n_experts=4, top_k=2)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    dep, report = export_quantized(p2)
    ffn = dep["units"]["u0"]["0"]["ffn"]
    dq = ffn["qp_wi"]
    assert isinstance(dq, DeployedQuantState)
    assert "wi" not in ffn  # float expert bank dropped
    E = cfg.n_experts
    assert dq.w_codes.shape[0] == E and dq.psum_exps.shape[0] == E
    assert report["unit.0.ffn.wi"]["n_experts"] == E

    x = jax.random.normal(jax.random.PRNGKey(2), (E, 3, cfg.d_model))
    y_o = execute_expert_gemm(dq, x, backend="oracle")
    y_p = execute_expert_gemm(dq, x, backend=PALLAS)
    np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_p))
    # per-expert slicing is exactly execute_gemm on each expert's codes
    import dataclasses
    for e in range(E):
        dqe = dataclasses.replace(
            dq, w_codes=dq.w_codes[e], ax_exp=dq.ax_exp[e],
            aw_exp=dq.aw_exp[e], psum_exps=dq.psum_exps[e])
        np.testing.assert_array_equal(
            np.asarray(y_o[e]),
            np.asarray(execute_gemm(dqe, x[e], backend="oracle")))


def test_moe_scan_stacked_expert_export_and_decode():
    """scan_layers=True (the default; olmoe/qwen3 shape): expert weights
    are [n_units, E, K, N] and must still export to per-expert deployed
    banks — regression for the export walk silently keeping float
    experts on stacked trees."""
    cfg = _cfg(mlp="moe", n_experts=4, top_k=2, scan_layers=True,
               n_layers=2)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    dep, report = export_quantized(p2)
    ffn = dep["units"]["0"]["ffn"]
    dq = ffn["qp_wi"]
    assert isinstance(dq, DeployedQuantState), type(dq)
    assert "wi" not in ffn
    assert dq.w_codes.shape[:2] == (cfg.n_units, cfg.n_experts)
    assert report["unit.0.ffn.wi"]["n_experts"] == cfg.n_experts
    # deployed forward (scan over units slices the expert banks per unit)
    lg_o = forward(dep, cfg, tok, backend="oracle")
    lg_p = forward(dep, cfg, tok, backend=PALLAS)
    np.testing.assert_array_equal(np.asarray(lg_o), np.asarray(lg_p))
    lg_fake = forward(snap_params_po2(p2), cfg, tok)
    err = float(jnp.max(jnp.abs(lg_o - lg_fake)))
    ref = float(jnp.max(jnp.abs(lg_fake))) + 1e-6
    assert err / ref < 0.05, (err, ref)


def test_moe_deployed_forward_matches_snapped_fakequant():
    cfg = _cfg(mlp="moe", n_experts=4, top_k=2)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    dep, _ = export_quantized(p2)
    lg_dep = forward(dep, cfg, tok, backend="oracle")
    lg_fake = forward(snap_params_po2(p2), cfg, tok)
    err = float(jnp.max(jnp.abs(lg_dep - lg_fake)))
    ref = float(jnp.max(jnp.abs(lg_fake))) + 1e-6
    assert err / ref < 0.05, (err, ref)


# ------------------------------ tied-embedding head ------------------------

def test_tied_head_calibrates_exports_and_serves():
    cfg = _cfg(tie_embeddings=True)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    assert "head" not in p  # tied: no separate head weight
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    qp_head = p2["embed"]["qp_head"]
    assert qp_head.name == "head" and qp_head.ap is not None
    dep, report = export_quantized(p2)
    dq = dep["embed"]["qp_head"]
    assert isinstance(dq, DeployedQuantState)
    assert report["head"]["tied_head"] and report["head"]["mode"] == "apsq"
    # the float table must survive for the input embedding lookup
    np.testing.assert_array_equal(np.asarray(dep["embed"]["table"]),
                                  np.asarray(p2["embed"]["table"]))
    # deployed logits == snapped fake-quant logits (same PO2 grid)
    lg_dep = forward(dep, cfg, tok, backend="oracle")
    lg_pal = forward(dep, cfg, tok, backend=PALLAS)
    np.testing.assert_array_equal(np.asarray(lg_dep), np.asarray(lg_pal))
    lg_fake = forward(snap_params_po2(p2), cfg, tok)
    err = float(jnp.max(jnp.abs(lg_dep - lg_fake)))
    ref = float(jnp.max(jnp.abs(lg_fake))) + 1e-6
    assert err / ref < 0.05, (err, ref)


# ------------------------------ kernel serving -----------------------------

def test_engine_pallas_decode_equals_oracle_decode():
    """The tentpole acceptance: ServingEngine.from_exported with
    the Pallas backend (interpret mode on CPU) greedy-decodes
    token-for-token identically to backend="oracle"."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg(tie_embeddings=True)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    prompt = np.arange(5) % cfg.vocab
    outs = {}
    for be in ("oracle", PallasBackend(interpret=True)):
        eng = ServingEngine.from_exported(p2, cfg, max_batch=1, cache_len=32,
                                          prefill_chunk=8, backend=be)
        done = eng.run([Request(uid=0, tokens=prompt, max_new_tokens=4)])
        outs[getattr(be, "name", be)] = done[0].out
    assert outs["oracle"] == outs["pallas"], outs


def test_engine_auto_backend_matches_oracle_on_cpu():
    """backend="auto" (the default) resolves to the oracle on CPU — the
    engine serves identically with no knob set."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    prompt = np.arange(4) % cfg.vocab
    outs = {}
    for be in ("auto", "oracle"):
        eng = ServingEngine.from_exported(p2, cfg, max_batch=1, cache_len=32,
                                          prefill_chunk=8, backend=be)
        outs[be] = eng.run([Request(uid=0, tokens=prompt,
                                    max_new_tokens=4)])[0].out
    assert outs["auto"] == outs["oracle"]


# ---------------------------------------------------------------------------
# kv_attention: the second op family
# ---------------------------------------------------------------------------

def test_kv_attention_op_family_backend_parity():
    """execute_kv_attention dispatches per backend; oracle == pallas ==
    auto (auto resolves to oracle on CPU) within interpret tolerance."""
    from repro.exec import execute_kv_attention
    from repro.kernels.int8_kv_attention import quantize_kv_po2

    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (2, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 16))
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    L = jnp.asarray([17, 64], jnp.int32)
    outs = {getattr(be, "name", be): execute_kv_attention(
                q, kc, vc, ke, ve, L, block_s=32, backend=be)
            for be in ("oracle", PALLAS, "auto")}
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["oracle"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(outs["auto"]),
                                  np.asarray(outs["oracle"]))


def test_kv_attention_scalar_length_and_block_rounding():
    """Scalar lengths broadcast; a non-dividing block_s is rounded down
    to a divisor of S instead of erroring (kv_block_size)."""
    from repro.exec import execute_kv_attention, kv_block_size
    from repro.kernels.int8_kv_attention import quantize_kv_po2

    assert kv_block_size(96, 512) == 96
    assert kv_block_size(96, 64) == 48
    assert kv_block_size(7, 4) == 1

    key = jax.random.PRNGKey(10)
    q = jax.random.normal(key, (1, 4, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 48, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 48, 2, 8))
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    a = execute_kv_attention(q, kc, vc, ke, ve, 20, block_s=20,
                             backend=PALLAS)
    b = execute_kv_attention(q, kc, vc, ke, ve,
                             jnp.asarray([20], jnp.int32), backend="oracle")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-6)


def _stacked_expert_state(key, n_experts, *, k=32, n=16, per_channel=True):
    """Stack E independently-exported linears into one expert-bank
    DeployedQuantState (what export_quantized emits for MoE wi/wo)."""
    import dataclasses
    xs, dqs = zip(*[_exported_linear(jax.random.fold_in(key, e), k=k, n=n,
                                     per_channel=per_channel)
                    for e in range(n_experts)])
    dq = dataclasses.replace(
        dqs[0],
        w_codes=jnp.stack([d.w_codes for d in dqs]),
        ax_exp=jnp.stack([d.ax_exp for d in dqs]),
        aw_exp=jnp.stack([d.aw_exp for d in dqs]),
        psum_exps=jnp.stack([d.psum_exps for d in dqs]))
    return jnp.stack(xs), dq, dqs


@pytest.mark.parametrize("n_experts", [1, 4, 8])
@pytest.mark.parametrize("per_channel,k", [(True, 32), (False, 45)])
def test_execute_expert_gemm_fused_equals_unrolled(n_experts, per_channel,
                                                   k):
    """The single fused expert launch == manually unrolled per-expert
    execute_gemm calls, on both backends, across expert counts, ragged K
    (45 % n_p != 0) and per-column exponent banks."""
    import dataclasses
    x, dq, dqs = _stacked_expert_state(
        jax.random.PRNGKey(11 + n_experts), n_experts, k=k,
        per_channel=per_channel)
    y_o = execute_expert_gemm(dq, x, backend="oracle")
    y_p = execute_expert_gemm(dq, x, backend=PallasBackend(interpret=True))
    np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_p))
    for e in range(n_experts):
        y_ref = execute_gemm(dqs[e], x[e], backend="oracle")
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_o[e]))


def test_expert_gemm_block_overrides_keep_parity():
    """PallasBackend block_overrides change launch geometry only — the
    fused expert output stays bit-identical."""
    from repro.kernels.autotune import BlockConfig
    x, dq, _ = _stacked_expert_state(jax.random.PRNGKey(17), 4)
    base = execute_expert_gemm(dq, x, backend=PallasBackend(interpret=True))
    pinned = execute_expert_gemm(
        dq, x, backend=PallasBackend(
            interpret=True,
            block_overrides={"expert": BlockConfig(8, 128,
                                                   source="override")}))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(pinned))
