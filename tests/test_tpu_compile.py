"""The main path's Pallas kernels compile for a TPU v5e at published widths.

Nothing runs: each test lowers a kernel entry point for one chip of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler
(Mosaic), which refuses what the Pallas interpreter accepts — block
shapes off the (8, 128) tiling, too much VMEM.  Shapes are those of
tinyllama-1.1b (d_model 2048, 32 query / 4 KV heads of 64, d_ff 5632,
vocab 32000) served by ``PagedServingEngine`` (page size 16, prefill
chunk 16), olmoe-1b-7b's expert banks (64 experts, d_ff 1024), and the
KV attention of the chip benchmark's deepseek-7b and chatglm3-6b.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test workers
must all collect the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.apsq_matmul import (
    apsq_expert_matmul_int8,
    apsq_matmul_int8,
    baseline_matmul_int8,
)

D, HQ, HKV, HD, FF, VOCAB = 2048, 32, 4, 64, 5632, 32000
PAGE, CHUNK = 16, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n,n_p,per_channel", [
    (CHUNK, D, D, 8, True),        # q / o projection, one prefill chunk
    (CHUNK, D, FF, 8, True),       # gate / up
    (CHUNK, FF, D, 8, True),       # down: K / n_p = 704, lane-padded
    (CHUNK, FF, D, 4, False),      # down at n_p = 4, per-tensor exponents
    (256, D, 2 * HKV * HD, 8, True),  # k / v at a long prefill
    (4, FF, D, 8, True),           # decode batch of 4 slots (small_m)
])
def test_apsq_generic_kernel_compiles(one_chip, m, k, n, n_p, per_channel):
    exps = (n_p, n) if per_channel else (n_p,)
    text = _compiled_text(
        lambda x, w, e: apsq_matmul_int8(x, w, e, gs=2), one_chip,
        ((m, k), jnp.int8), ((k, n), jnp.int8), (exps, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n,n_p", [
    (D, VOCAB, 8),                 # the 32000-wide head
    (FF, D, 8),                    # down projection
    (D, FF, 4),
])
def test_apsq_m1_decode_kernel_compiles(one_chip, k, n, n_p):
    text = _compiled_text(
        lambda x, w, e: apsq_matmul_int8(x, w, e, gs=2), one_chip,
        ((1, k), jnp.int8), ((k, n), jnp.int8), ((n_p, n), jnp.int32))
    assert "tpu_custom_call" in text


def test_w8a8_baseline_kernel_compiles(one_chip):
    text = _compiled_text(
        lambda x, w: baseline_matmul_int8(x, w, n_p=8), one_chip,
        ((CHUNK, FF), jnp.int8), ((FF, D), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,chunk,seq,hq,hkv,hd", [
    pytest.param(8, 0, 1024, HQ, HKV, HD,
                 id="8-0-1024"),   # decode: 8 slots of 64 pages
    pytest.param(4, 0, 16, HQ, HKV, HD,
                 id="4-0-16"),     # decode over a single page
    pytest.param(1, CHUNK, 1024, HQ, HKV, HD,
                 id="1-16-1024"),  # chunked prefill, one query block
    pytest.param(1, 8, 64, HQ, HKV, HD, id="1-8-64"),
    # The chip benchmark's cells: 16 slots of 40 pages of 16.
    pytest.param(16, 0, 640, 32, 32, 128, id="deepseek-7b-decode"),
    pytest.param(16, 0, 640, 32, 2, 128, id="chatglm3-6b-decode"),
    pytest.param(1, CHUNK, 640, 32, 32, 128, id="deepseek-7b-chunk"),
    pytest.param(1, CHUNK, 640, 32, 2, 128, id="chatglm3-6b-chunk"),
])
def test_int8_kv_attention_compiles(one_chip, batch, chunk, seq, hq, hkv,
                                    hd):
    """With the KV tile the Pallas backend resolves from the shapes
    (many pages, several heads a step, the scalar-prefetched lengths)."""
    from repro.exec import PallasBackend
    q = (batch, hq, hd) if chunk == 0 else (batch, chunk, hq, hd)
    text = _compiled_text(PallasBackend().kv_attention, one_chip,
                          (q, jnp.bfloat16),
                          ((batch, seq, hkv, hd), jnp.int8),
                          ((batch, seq, hkv, hd), jnp.int8),
                          ((batch, hkv), jnp.int32),
                          ((batch, hkv), jnp.int32), ((batch,), jnp.int32))
    assert "tpu_custom_call" in text


def test_scoped_kernel_keeps_the_name_the_benchmark_matches(one_chip):
    """Under a ``jax.named_scope`` the kernel's custom call keeps the name
    the chip benchmark's trace reduction matches
    (``chipbench.trace.KERNELS``), and carries the scope in its
    metadata."""
    from chipbench.trace import KERNELS, op_family

    def f(x, w, e):
        with jax.named_scope("apsq_gemm"):
            return apsq_matmul_int8(x, w, e, gs=2)

    text = _compiled_text(f, one_chip, ((1, D), jnp.int8),
                          ((D, FF), jnp.int8), ((8, FF), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln).group(1)
        assert KERNELS["apsq_gemm"].match(op_family(name)), name
        assert re.search(r'op_name="[^"]*/apsq_gemm/', ln), ln


def test_scoped_kv_kernel_keeps_the_name_the_benchmark_matches(one_chip):
    """The INT8 KV-attention kernel, launched as the paged decode launches
    it, keeps the op name ``chipbench.trace.KERNELS["kv_attn"]`` matches,
    under its ``kv_attention`` scope."""
    from chipbench.trace import KERNELS, op_family
    from repro.exec import PallasBackend, execute_kv_attention

    def f(q, k, v, ke, ve, n):
        return execute_kv_attention(q, k, v, ke, ve, n,
                                    backend=PallasBackend())

    b, s = 16, 40 * PAGE
    text = _compiled_text(f, one_chip, ((b, HQ, HD), jnp.float32),
                          ((b, s, HKV, HD), jnp.int8),
                          ((b, s, HKV, HD), jnp.int8),
                          ((b, HKV), jnp.int32), ((b, HKV), jnp.int32),
                          ((b,), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln).group(1)
        assert KERNELS["kv_attn"].match(op_family(name)), name
        assert re.search(r'op_name="[^"]*kv_attention/', ln), ln


@pytest.mark.parametrize("k,n", [(D, 1024), (1024, D)])
def test_apsq_fused_expert_kernel_compiles(one_chip, k, n):
    """olmoe-1b-7b: 64 experts, d_model 2048, expert d_ff 1024 (the wi /
    wg banks, then wo), capacity 8 rows."""
    e, m, n_p = 64, 8, 8
    text = _compiled_text(
        lambda x, w, ex: apsq_expert_matmul_int8(x, w, ex, gs=2), one_chip,
        ((e, m, k), jnp.int8), ((e, k, n), jnp.int8),
        ((e, n_p, n), jnp.int32))
    assert "tpu_custom_call" in text
