"""Tensor/expert-parallel integer serving (``repro.dist.tp``).

Three levels, all bit-exact by construction (PO2 grids, integer
arithmetic):

  * plan level — ``plan_gemm`` picks the shard axis Algorithm-1 allows
    (K by whole PSUM tiles for PSQ/W8A8, N for APSQ's sequential chain)
    with divisibility fallbacks;
  * GEMM level — ``ShardedBackend`` over a 2/8-device host mesh returns
    the same integers as the single-device oracle for every mode x
    exponent layout x wire flag;
  * engine level — ``PagedServingEngine.from_exported(mesh=...)`` greedy
    decode is token-identical (and KV pool/exponent identical) to the
    single-device engine, for dense, MoE expert-parallel and per-column
    exponent exports, on both wire modes.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/conftest.py sets it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantConfig
from repro.dist.tp import (GemmPlan, plan_gemm, shard_deployed,
                           wire_report)
from repro.exec import PallasBackend, ShardedBackend, get_backend
from repro.kernels.apsq_matmul.ref import choose_exps
from repro.launch.mesh import make_smoke_mesh
from repro.models.config import ModelConfig
from repro.models.model import init_lm, lm_specs
from repro.quant import calibrate_model, export_quantized
from repro.serving import PagedServingEngine, Request

needs2 = pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


# ---------------------------------------------------------------------------
# Plan level
# ---------------------------------------------------------------------------

def test_plan_gemm_axis_by_mode():
    # PSQ: K by whole PSUM tiles whenever n_p divides
    assert plan_gemm(k=32, n=16, n_p=4, gs=4, d=2) == GemmPlan("k", "psq", 2)
    # gs >= n_p EXECUTES as psq even if declared apsq
    assert plan_gemm(k=32, n=16, n_p=4, gs=8, d=2).mode == "psq"
    # APSQ: sequential chain along K -> column-parallel
    assert plan_gemm(k=32, n=16, n_p=4, gs=2, d=2) == GemmPlan("n", "apsq", 2)
    # W8A8: exact int32 psum over K spans
    assert plan_gemm(k=32, n=16, n_p=None, gs=1, d=2) == \
        GemmPlan("k", "w8a8", 2)


def test_plan_gemm_fallbacks():
    # psq with n_p % d != 0 -> N; N % d != 0 too -> replicate
    assert plan_gemm(k=32, n=16, n_p=3, gs=3, d=2).axis == "n"
    assert plan_gemm(k=32, n=15, n_p=3, gs=3, d=2).axis == "replicate"
    # w8a8 ragged K -> N
    assert plan_gemm(k=33, n=16, n_p=None, gs=1, d=2).axis == "n"
    # single device: always replicate, never sharded
    p = plan_gemm(k=32, n=16, n_p=4, gs=4, d=1)
    assert p.axis == "replicate" and not p.sharded


# ---------------------------------------------------------------------------
# Mesh construction (launch.mesh honoring requested shapes)
# ---------------------------------------------------------------------------

def test_smoke_mesh_default_spans_all_devices():
    mesh = make_smoke_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == len(jax.devices())


@needs2
def test_smoke_mesh_honors_requested_shape():
    mesh = make_smoke_mesh((1, 2))
    assert dict(mesh.shape) == {"data": 1, "model": 2}


@needs8
def test_smoke_mesh_multi_pod_shape():
    mesh = make_smoke_mesh((2, 2, 2), ("pod", "data", "model"))
    assert dict(mesh.shape) == {"pod": 2, "data": 2, "model": 2}


def test_smoke_mesh_rejects_bad_requests():
    with pytest.raises(ValueError, match="rank mismatch"):
        make_smoke_mesh((2, 2, 2))           # 3 dims, 2 default axes
    with pytest.raises(ValueError, match="devices"):
        make_smoke_mesh((1, 4096))


# ---------------------------------------------------------------------------
# GEMM level: sharded == oracle, every mode/layout/wire
# ---------------------------------------------------------------------------

def _gemm_case(k, n, n_p, gs, per_col, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.randint(key, (4, k), -128, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128,
                           jnp.int8)
    exps = None
    if n_p is not None:
        exps = choose_exps(x, w, n_p=n_p, gs=gs)
        if per_col:
            exps = jnp.broadcast_to(exps[:, None], (n_p, n))
    return x, w, exps


GEMM_CASES = [
    # (tag,        k,  n, n_p, gs, per_col)
    ("apsq",       32, 16, 4, 2, False),
    ("apsq-pcol",  32, 16, 4, 2, True),
    ("psq",        32, 16, 4, 4, False),
    ("psq-pcol",   32, 16, 4, 4, True),
    ("psq-ragged", 36, 16, 4, 4, False),   # K % n_p != 0 zero-pad tail
    ("w8a8",       32, 16, None, 1, False),
]


@needs2
@pytest.mark.parametrize("tag,k,n,n_p,gs,per_col", GEMM_CASES,
                         ids=[c[0] for c in GEMM_CASES])
@pytest.mark.parametrize("wire", ["int8", "fp32"])
def test_sharded_gemm_matches_oracle(tag, k, n, n_p, gs, per_col, wire):
    x, w, exps = _gemm_case(k, n, n_p, gs, per_col)
    ref = get_backend("oracle").int_gemm(x, w, exps, gs=gs)
    mesh = make_smoke_mesh((1, 2))
    be = ShardedBackend(mesh=mesh, inner="oracle", wire=wire)
    y = be.int_gemm(x, w, exps, gs=gs)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


@needs8
@pytest.mark.parametrize("tag,k,n,n_p,gs,per_col",
                         [GEMM_CASES[0], GEMM_CASES[2], GEMM_CASES[5]],
                         ids=["apsq", "psq", "w8a8"])
def test_sharded_gemm_matches_oracle_8dev(tag, k, n, n_p, gs, per_col):
    x, w, exps = _gemm_case(k, n, n_p, gs, per_col)
    ref = get_backend("oracle").int_gemm(x, w, exps, gs=gs)
    # n_p=4 < 8 devices: psq K-shard misses divisibility -> N fallback;
    # parity must hold through the fallback chain too.
    be = ShardedBackend(mesh=make_smoke_mesh((1, 8)), inner="oracle")
    np.testing.assert_array_equal(np.asarray(be.int_gemm(x, w, exps, gs=gs)),
                                  np.asarray(ref))


@needs8
def test_sharded_gemm_on_multi_pod_mesh():
    """Full-manual over all axes: axis_index in the bodies must not trip
    GSPMD's PartitionId limitation when idle pod/data axes exist."""
    x, w, exps = _gemm_case(32, 16, 4, 2, True)  # per-col exercises idx
    ref = get_backend("oracle").int_gemm(x, w, exps, gs=2)
    mesh = make_smoke_mesh((2, 2, 2), ("pod", "data", "model"))
    be = ShardedBackend(mesh=mesh, inner="oracle")
    np.testing.assert_array_equal(np.asarray(be.int_gemm(x, w, exps, gs=2)),
                                  np.asarray(ref))


@needs2
@pytest.mark.parametrize("wire", ["int8", "fp32"])
def test_sharded_expert_gemm_matches_oracle(wire):
    key = jax.random.PRNGKey(3)
    E, M, K, N, n_p, gs = 4, 2, 32, 16, 4, 2
    x = jax.random.randint(key, (E, M, K), -128, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (E, K, N),
                           -128, 128, jnp.int8)
    exps = jnp.stack([choose_exps(x[e], w[e], n_p=n_p, gs=gs)
                      for e in range(E)])
    ref = get_backend("oracle").int_expert_gemm(x, w, exps, gs=gs)
    be = ShardedBackend(mesh=make_smoke_mesh((1, 2)), inner="oracle",
                        wire=wire)
    np.testing.assert_array_equal(
        np.asarray(be.int_expert_gemm(x, w, exps, gs=gs)), np.asarray(ref))


def test_sharded_backend_rejects_bad_wire():
    with pytest.raises(ValueError, match="wire"):
        ShardedBackend(wire="int7")


def test_sharded_backend_meshless_delegates():
    # the registered instance has no mesh: pure delegation to inner
    x, w, exps = _gemm_case(32, 16, 4, 2, False)
    y = get_backend("sharded").int_gemm(x, w, exps, gs=2)
    ref = get_backend("oracle").int_gemm(x, w, exps, gs=2)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


# ---------------------------------------------------------------------------
# Placement + spec tooling on exported trees
# ---------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(name="tp", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, dtype="float32",
                scan_layers=False, quant=QuantConfig.apsq(gs=2, n_p=4))
    base.update(kw)
    return ModelConfig(**base)


def _exported(cfg, seed=0):
    p = init_lm(jax.random.PRNGKey(seed), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 16), 0,
                             cfg.vocab)
    return export_quantized(calibrate_model(p, cfg, {"tokens": tok}))[0]


@needs2
def test_shard_deployed_places_and_reports():
    cfg = _cfg()
    dep = _exported(cfg)
    mesh = make_smoke_mesh((1, 2))
    placed, plans = shard_deployed(dep, mesh)
    # same tree structure, arrays committed to the mesh
    assert jax.tree.structure(placed) == jax.tree.structure(dep)
    assert plans, "expected a non-empty plan dict"
    assert any(pl.axis != "replicate" for pl in plans.values())
    # placement matches plan_gemm on every planned GEMM
    for name, pl in plans.items():
        if pl.kind == "attn":
            continue
        assert pl.axis == plan_gemm(k=pl.k, n=pl.n, n_p=pl.n_p, gs=pl.gs,
                                    d=pl.d).axis, name
    # the analytic report aggregates and the PSUM-mode combines switch
    wr = wire_report(plans, m=1)
    assert wr["switchable"]["ratio"] is not None
    assert wr["switchable"]["ratio"] >= 3.5
    # values are untouched by placement (device_put only)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), dep, placed)


def test_tree_specs_handles_deployed_tree():
    from repro.core import DeployedQuantState
    from repro.dist import tree_specs
    cfg = _cfg(scan_layers=True)
    dep = _exported(cfg)
    mesh = make_smoke_mesh()
    specs = tree_specs(lm_specs(cfg), dep, mesh)
    # params structure preserved (jit in_shardings ready)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, specs)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, dep))

    found = []

    def walk(sp, dq):
        if isinstance(dq, DeployedQuantState):
            found.append(sp)
            assert sp.ax_exp == jax.sharding.PartitionSpec()
            assert sp.aw_exp == jax.sharding.PartitionSpec()
            assert isinstance(sp.w_codes, jax.sharding.PartitionSpec)
        elif isinstance(dq, dict):
            for k in dq:
                walk(sp[k], dq[k])

    walk(specs, dep)
    assert found, "no DeployedQuantState leaves visited"


# ---------------------------------------------------------------------------
# Engine level: the acceptance gate
# ---------------------------------------------------------------------------

def _decode(params, cfg, mesh=None, wire="int8", backend="oracle"):
    eng = PagedServingEngine.from_exported(
        params, cfg, max_batch=2, page_size=8, n_pages=16, prefill_chunk=8,
        backend=backend, mesh=mesh, wire=wire)
    prompts = [((np.arange(n) * 7 + s * 13) % cfg.vocab).astype(np.int32)
               for n, s in ((5, 0), (9, 1))]
    done = eng.run([Request(uid=i, tokens=p, max_new_tokens=5)
                    for i, p in enumerate(prompts)])
    outs = tuple(tuple(r.out) for r in sorted(done, key=lambda r: r.uid))
    return outs, jax.tree.map(np.asarray, jax.device_get(eng.state))


ENGINE_CASES = {
    # per_channel_w=True (default) exports per-column [n_p, N] exponents
    "dense-percol": dict(),
    "dense": dict(quant=QuantConfig(
        enabled=True, per_channel_w=False,
        psum=QuantConfig.apsq(gs=2, n_p=4).psum)),
    "moe-ep": dict(mlp="moe", n_experts=4, top_k=2),
}


@needs2
@pytest.mark.parametrize("case", list(ENGINE_CASES))
def test_engine_sharded_decode_matches_single_device(case):
    """ISSUE acceptance: greedy decode through the sharded engine is
    token-identical AND KV-pool/exponent identical to single-device, on
    both wire modes."""
    cfg = _cfg(**ENGINE_CASES[case])
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    ref_outs, ref_state = _decode(p2, cfg)
    for wire in ("int8", "fp32"):
        outs, state = _decode(p2, cfg, mesh=make_smoke_mesh((1, 2)),
                              wire=wire)
        assert outs == ref_outs, (case, wire)
        jax.tree.map(np.testing.assert_array_equal, ref_state, state)
    if case == "dense-percol":
        # The acceptance bar is parity with the single-device *pallas*
        # backend: pin oracle == pallas here, and run the sharded engine
        # with the pallas kernel as the per-shard inner once.
        pal = PallasBackend(interpret=True)
        pal_outs, pal_state = _decode(p2, cfg, backend=pal)
        assert pal_outs == ref_outs
        jax.tree.map(np.testing.assert_array_equal, ref_state, pal_state)
        outs, state = _decode(p2, cfg, mesh=make_smoke_mesh((1, 2)),
                              backend=pal)
        assert outs == ref_outs
        jax.tree.map(np.testing.assert_array_equal, ref_state, state)


@needs8
def test_engine_sharded_decode_matches_single_device_8dev():
    cfg = _cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    p2 = calibrate_model(p, cfg, {"tokens": tok})
    ref_outs, ref_state = _decode(p2, cfg)
    outs, state = _decode(p2, cfg, mesh=make_smoke_mesh((1, 8)))
    assert outs == ref_outs
    jax.tree.map(np.testing.assert_array_equal, ref_state, state)


# ---------------------------------------------------------------------------
# Gradient compression: bits routing (satellite of the same wire story)
# ---------------------------------------------------------------------------

def test_int4_pack_roundtrip_exact():
    from repro.dist import pack_int4, unpack_int4
    codes = jnp.arange(-8, 8, dtype=jnp.int8).reshape(4, 4)
    packed = pack_int4(codes)
    assert packed.size == 8                 # two codes per byte
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(packed, codes.size, codes.shape)),
        np.asarray(codes))
    odd = jnp.asarray([-8, 7, 3], jnp.int8)  # odd length pads
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(pack_int4(odd), 3, (3,))), np.asarray(odd))


def test_compress_tree_psum_rejects_unknown_bits():
    from repro.dist import compress_tree_psum
    with pytest.raises(ValueError, match="bits"):
        compress_tree_psum({"g": jnp.ones(4)}, "pod", bits=3)


@needs2
@pytest.mark.parametrize("bits", [4, 8])
def test_compress_tree_psum_wire_accounting(bits):
    from repro.dist import compress_tree_psum
    from repro.dist.sharding import shard_map
    mesh = make_smoke_mesh((2, 1), ("pod", "data"))
    g = {"a": jnp.linspace(-1, 1, 64).reshape(8, 8),
         "b": jnp.linspace(-2, 2, 10)}
    info_box = {}

    def body(tree):
        out, info = compress_tree_psum(tree, "pod", bits=bits)
        info_box.update(info)
        return out

    from jax.sharding import PartitionSpec as P
    f = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                  axis_names={"pod"})
    out = jax.jit(f)(g)
    assert info_box["bits"] == bits
    # 74 elements: 8-bit -> 74 code bytes, 4-bit -> 37; +4B scale per leaf
    assert info_box["wire_bytes"] == (74 * bits + 7) // 8 + 8
    assert info_box["fp32_bytes"] == 4 * 74
    # identical grads on every pod replica -> mean of quantized == quantized;
    # 4-bit is coarser but still finite and close
    for k in g:
        err = float(jnp.max(jnp.abs(out[k] - g[k])))
        assert err <= (2.0 if bits == 4 else 0.5) * 2 / (2 ** (bits - 1) - 1)
