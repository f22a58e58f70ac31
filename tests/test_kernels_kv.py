"""INT8 KV-cache decode attention kernel vs oracle + fp32 tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.int8_kv_attention import (
    cache_bytes,
    fp_attention_ref,
    int8_kv_attention,
    int8_kv_attention_f32,
    int8_kv_attention_ref,
    quantize_kv_po2,
)

KEY = jax.random.PRNGKey(0)


def _case(B, S, Hq, Hkv, hd, length=None, seed=0):
    k0 = jax.random.fold_in(KEY, seed)
    q = jax.random.normal(k0, (B, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, Hkv, hd))
    length = jnp.full((B,), length if length is not None else S, jnp.int32)
    return q, k, v, length


@pytest.mark.parametrize("B,S,Hq,Hkv,hd,block_s", [
    (2, 64, 4, 2, 16, 32),
    (1, 128, 8, 1, 32, 128),   # MQA
    (2, 96, 4, 4, 16, 32),     # MHA
    (1, 64, 6, 2, 8, 16),
    (2, 640, 16, 1, 16, 320),  # G = 16; a tile spans 20 pages of 16
    (2, 640, 4, 4, 16, 320),   # G = 1 (MHA): one-row tiles, all heads a step
    (1, 1024, 16, 16, 128, 512),  # G = 1: two groups of 8 heads, two tiles
])
def test_kernel_matches_oracle(B, S, Hq, Hkv, hd, block_s):
    q, k, v, length = _case(B, S, Hq, Hkv, hd)
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    ref = int8_kv_attention_ref(q, kc, vc, ke, ve, length)
    out = int8_kv_attention(q, kc, vc, ke, ve, length, block_s=block_s,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_partial_cache_length_masked():
    q, k, v, _ = _case(2, 64, 4, 2, 16, seed=3)
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    L = jnp.asarray([17, 40], jnp.int32)
    ref = int8_kv_attention_ref(q, kc, vc, ke, ve, L)
    out = int8_kv_attention(q, kc, vc, ke, ve, L, block_s=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # junk beyond L must not leak: perturb the masked region, same output
    kc2 = kc.at[:, 50:].set(127)
    out2 = int8_kv_attention(q, kc2, vc, ke, ve, L, block_s=32,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-6, atol=1e-7)


def test_int8_path_close_to_fp32():
    q, k, v, length = _case(2, 128, 8, 2, 32, seed=5)
    fp = fp_attention_ref(q, k, v, length)
    out = int8_kv_attention_f32(q, k, v, length, block_s=64,
                                interpret=True)
    rel = float(jnp.mean(jnp.abs(out - fp)) / jnp.mean(jnp.abs(fp)))
    assert rel < 0.03, rel  # ~8-bit cache quantization noise


def test_quantize_roundtrip_po2():
    x = jax.random.normal(KEY, (2, 32, 4, 16)) * 3
    codes, exp = quantize_kv_po2(x)
    assert codes.dtype == jnp.int8 and exp.shape == (2, 4)
    from repro.kernels.int8_kv_attention import dequantize_kv_po2
    back = dequantize_kv_po2(codes, exp)
    rel = float(jnp.mean(jnp.abs(back - x)) / jnp.mean(jnp.abs(x)))
    assert rel < 0.02  # PO2 scales are up to 2x coarser than optimal
    # scales are powers of two (shift-dequant in hardware)
    s = np.exp2(np.asarray(exp, np.float64))
    assert np.all(np.log2(s) == np.round(np.log2(s)))


def test_cache_bytes_halved():
    b = cache_bytes(8, 32768, 4, 128)
    assert b["int8"] < b["bf16"] * 0.51


# A tile of 64 spans four pages of 16; the batch is ragged: beside the
# case's length, one slot is full (S = 128) and one holds the least a
# query sees (1 in decode, the chunk in the chunk form).
_EDGE = [(n, g, c) for g in (1, 16) for c in (0, 4)
         for n in (c or 1, 63, 64, 65, 128)]


@pytest.mark.parametrize("length,group,chunk", [
    pytest.param(32, 2, 0, id="32"),   # exactly one block
    pytest.param(33, 2, 0, id="33"),   # length % block_s == 1
    pytest.param(1, 2, 0, id="1"),     # first position only
    pytest.param(64, 2, 0, id="64"),   # every block full
] + [pytest.param(n, g, c, id=f"{n}-G{g}-{'chunk' if c else 'decode'}")
     for n, g, c in _EDGE])
def test_block_s_boundary_lengths(length, group, chunk):
    """Valid-length mask at block edges: the online-softmax carry must
    neither drop the last valid position nor admit the first masked one,
    and tiles past a slot's length, which the kernel skips, change
    nothing."""
    if group == 2:
        B, S, Hq, Hkv, block_s = 2, 64, 4, 2, 32
    else:
        B, S, Hq, Hkv, block_s = 3, 128, 16, 16 // group, 64
    q, k, v, _ = _case(B, S, Hq, Hkv, 16, seed=7)
    if chunk:
        q = jax.random.normal(jax.random.fold_in(KEY, 70),
                              (B, chunk, Hq, 16))
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    L = jnp.asarray([length, S, chunk or 1][:B], jnp.int32)
    ref = int8_kv_attention_ref(q, kc, vc, ke, ve, L)
    out = int8_kv_attention(q, kc, vc, ke, ve, L, block_s=block_s,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_ragged_lengths_across_batch():
    """Every batch row at a different fill level (the continuous-batching
    shape: slots admitted at different times), including block boundaries."""
    q, k, v, _ = _case(4, 96, 4, 2, 16, seed=8)
    kc, ke = quantize_kv_po2(k)
    vc, ve = quantize_kv_po2(v)
    L = jnp.asarray([1, 32, 33, 96], jnp.int32)
    ref = int8_kv_attention_ref(q, kc, vc, ke, ve, L)
    out = int8_kv_attention(q, kc, vc, ke, ve, L, block_s=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # rows are independent: rerunning row 0 alone reproduces its output
    solo = int8_kv_attention(q[:1], kc[:1], vc[:1], ke[:1], ve[:1],
                             L[:1], block_s=32, interpret=True)
    np.testing.assert_allclose(np.asarray(solo[0]), np.asarray(out[0]),
                               rtol=1e-6, atol=1e-7)


def test_paged_calls_resolve_one_tile(monkeypatch):
    """The paged decode, the stable chunk and the replayed chunk launch
    the kernel with one KV tile for one S, the one ``kv_tile`` resolves
    from shapes: many pages, not one."""
    import repro.kernels.int8_kv_attention as kv
    from repro.exec import PallasBackend, kv_tile
    from repro.serving.paged_cache import (
        EXP_FLOOR,
        paged_prefill_chunk_update_and_attend,
        paged_update_and_attend,
    )
    seen = []
    real = kv.int8_kv_attention

    def spy(q, *a, block_s, **kw):
        seen.append((q.ndim, block_s))
        return real(q, *a, block_s=block_s, **kw)

    monkeypatch.setattr(kv, "int8_kv_attention", spy)
    page, n_max, hq, hkv, hd = 16, 40, 4, 2, 8
    cache = {"k_pages": jnp.zeros((n_max + 1, page, hkv, hd), jnp.int8),
             "v_pages": jnp.zeros((n_max + 1, page, hkv, hd), jnp.int8),
             "k_exp": jnp.full((1, hkv), EXP_FLOOR, jnp.int32),
             "v_exp": jnp.full((1, hkv), EXP_FLOOR, jnp.int32)}
    table = jnp.arange(1, n_max + 1, dtype=jnp.int32)[None]
    be = PallasBackend(interpret=True)
    key = jax.random.fold_in(KEY, 11)
    x = jax.random.normal(key, (1, 4, hq + 2 * hkv, hd))
    # Growing magnitudes bump the exponents mid-chunk: the replay runs.
    x = x * jnp.arange(1, 5, dtype=jnp.float32)[None, :, None, None]
    q, kn, vn = x[:, :, :hq], x[:, :, hq:hq + hkv], x[:, :, hq + hkv:]
    _, cache = paged_prefill_chunk_update_and_attend(
        cache, q, kn, vn, jnp.asarray([0]), table, backend=be)
    paged_update_and_attend(cache, q[:, 0], kn[:, :1], vn[:, :1],
                            jnp.asarray([4]), table, backend=be)
    tile = kv_tile(n_max * page, hd, hq // hkv)
    assert tile == 320
    assert sorted(set(seen)) == [(3, tile), (4, tile)], seen
    assert len(seen) == 3          # stable chunk, replay step, decode
