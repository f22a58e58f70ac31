"""Pallas APSQ kernel: bit-exact vs the pure-jnp integer oracle.

Sweeps shapes / gs / n_p / adversarial exponents in interpret mode (the
kernel body executes in Python on CPU; on TPU the same BlockSpecs run on
hardware).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.apsq_matmul import (
    accumulator_vmem_bytes,
    apsq_matmul_f32,
    apsq_matmul_int8,
    apsq_matmul_ref,
    baseline_matmul_int8,
    baseline_matmul_ref,
    choose_exps,
    dequantize_psum,
    quantize_psum,
    rshift_round,
)

def _codes(key, shape):
    return jax.random.randint(key, shape, -128, 128, jnp.int8)


@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (16, 64, 32), (32, 128, 128),
                                   (8, 40, 16), (130, 96, 130)])
@pytest.mark.parametrize("gs", [1, 2, 3, 4])
def test_kernel_bit_exact_vs_oracle(m, k, n, gs):
    key = jax.random.PRNGKey(m * 1000 + k + gs)
    for n_p in (1, 2, 4):
        if k % n_p:
            continue
        x = _codes(key, (m, k))
        w = _codes(jax.random.fold_in(key, 1), (k, n))
        exps = choose_exps(x, w, n_p=n_p, gs=gs)
        ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
        out = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@given(st.integers(1, 8), st.integers(1, 5), st.integers(0, 10))
def test_kernel_property_shapes(n_p, gs, seed):
    key = jax.random.PRNGKey(seed)
    m, n = 8, 16
    k = n_p * 8
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    exps = choose_exps(x, w, n_p=n_p, gs=gs)
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    out = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_adversarial_exponents():
    """Extreme exponents (0 and large) must clip/shift identically."""
    key = jax.random.PRNGKey(7)
    x = _codes(key, (8, 32))
    w = _codes(jax.random.fold_in(key, 1), (32, 16))
    for exps in ([0, 0, 0, 0], [20, 20, 20, 20], [0, 20, 0, 20]):
        e = jnp.asarray(exps, jnp.int32)
        ref = apsq_matmul_ref(x, w, e, n_p=4, gs=2)
        out = apsq_matmul_int8(x, w, e, gs=2, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_baseline_kernel_equals_int_matmul():
    key = jax.random.PRNGKey(8)
    x = _codes(key, (16, 64))
    w = _codes(jax.random.fold_in(key, 1), (64, 32))
    out = baseline_matmul_int8(x, w, n_p=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(baseline_matmul_ref(x, w)),
                                  np.asarray(out))


def test_rshift_round_half_up():
    v = jnp.asarray([5, -5, 6, -6, 7], jnp.int32)
    # (v + 2) >> 2 == round-half-up(v / 4): 1.25->1, -1.25->-1, 1.5->2,
    # -1.5->-1 (half rounds toward +inf), 1.75->2
    np.testing.assert_array_equal(np.asarray(rshift_round(v, 2)),
                                  [1, -1, 2, -1, 2])
    np.testing.assert_array_equal(np.asarray(rshift_round(v, 0)),
                                  np.asarray(v))


def test_quant_dequant_roundtrip_within_one_lsb():
    v = jnp.arange(-500, 500, 7, dtype=jnp.int32)
    e = jnp.asarray(3, jnp.int32)
    code = quantize_psum(v, e)
    back = dequantize_psum(code, e)
    assert int(jnp.max(jnp.abs(back - v))) <= 2 ** 3 // 2 + 1


def test_apsq_error_bounded_vs_exact():
    """APSQ output within a few shifted LSBs of the exact INT32 GEMM."""
    key = jax.random.PRNGKey(9)
    x = _codes(key, (16, 64))
    w = _codes(jax.random.fold_in(key, 1), (64, 32))
    exact = baseline_matmul_ref(x, w)
    for gs in (1, 2, 4):
        exps = choose_exps(x, w, n_p=8, gs=gs)
        out = apsq_matmul_ref(x, w, exps, n_p=8, gs=gs)
        lsb = 2.0 ** float(jnp.max(exps))
        err = float(jnp.max(jnp.abs((out - exact))))
        assert err <= lsb * (8 / gs + 2), (gs, err, lsb)


def test_f32_wrapper_scales():
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (8, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16)) * 0.1
    ax = float(jnp.max(jnp.abs(x))) / 127
    aw = float(jnp.max(jnp.abs(w))) / 127
    xq = jnp.clip(jnp.round(x / ax), -128, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / aw), -128, 127).astype(jnp.int8)
    exps = choose_exps(xq, wq, n_p=4, gs=2)
    y = apsq_matmul_f32(x, w, exps, gs=2, ax=ax, aw=aw, interpret=True)
    rel = float(jnp.mean(jnp.abs(y - x @ w)) / jnp.mean(jnp.abs(x @ w)))
    assert rel < 0.1, rel


def test_accumulator_working_set():
    b = accumulator_vmem_bytes(128, 128, gs=1)
    assert b["apsq_banks"] * 4 == b["baseline_int32"]  # beta 4 -> 1
    b4 = accumulator_vmem_bytes(128, 128, gs=4)
    assert b4["apsq_banks"] == b4["baseline_int32"]  # parity at gs=4


# ---------------------------------------------------------------------------
# Kernel-vs-oracle parity grid: serving shapes, ragged K, exponent layouts
# ---------------------------------------------------------------------------

# (m, k) cells: decode M=1, small/batched prefill M, ragged K (K % n_p != 0
# for some n_p below -> remainder PSUM group), and an unaligned N.
PARITY_SHAPES = [
    (1, 64, 32),     # decode: one token against the cache
    (1, 37, 16),     # decode + ragged K for every n_p > 1
    (8, 40, 24),     # small batch, ragged for n_p in (3, 16)
    (64, 96, 48),    # batched prefill
    (130, 100, 130), # prefill crossing block_m/block_n boundaries, ragged
]


@pytest.mark.parametrize("m,k,n", PARITY_SHAPES)
@pytest.mark.parametrize("gs", [1, 2, 4])
@pytest.mark.parametrize("n_p", [1, 3, 4, 16])
def test_parity_grid_kernel_vs_oracle(m, k, n, gs, n_p):
    """The full serving grid: every (shape, gs, n_p) cell bit-exact,
    including ragged K handled by the zero-contribution remainder group."""
    key = jax.random.PRNGKey(m * 7919 + k * 31 + n_p * 7 + gs)
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    exps = choose_exps(x, w, n_p=n_p, gs=gs)
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    out = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("m,k,n,n_p,gs", [(8, 32, 16, 4, 2),
                                          (1, 48, 16, 4, 3),
                                          (16, 64, 130, 8, 2),
                                          (4, 30, 20, 4, 2)])
def test_parity_per_column_exponents(m, k, n, n_p, gs):
    """[n_p, N] exponents (per-channel weight-scale export layout): the
    kernel's VMEM exponent block must match the broadcasting oracle."""
    key = jax.random.PRNGKey(m + k + n)
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    base = choose_exps(x, w, n_p=n_p, gs=gs)
    exps = base[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :] % 3
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    out = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_ragged_k_equals_explicitly_padded():
    """Ragged K == running the kernel on zero-padded codes (the remainder
    group contributes nothing)."""
    from repro.kernels.apsq_matmul import pad_ragged_k
    key = jax.random.PRNGKey(13)
    x = _codes(key, (8, 45))
    w = _codes(jax.random.fold_in(key, 1), (45, 16))
    n_p, gs = 4, 2
    exps = choose_exps(x, w, n_p=n_p, gs=gs)
    xp, wp = pad_ragged_k(x, w, n_p)
    assert xp.shape[1] == 48 and wp.shape[0] == 48
    ragged = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
    padded = apsq_matmul_int8(xp, wp, exps, gs=gs, interpret=True)
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(padded))
    base = baseline_matmul_int8(x, w, n_p=n_p, interpret=True)
    np.testing.assert_array_equal(np.asarray(base),
                                  np.asarray(baseline_matmul_ref(x, w)))


@pytest.mark.parametrize("k,n_p", [(45, 4), (5632 // 8, 8), (300, 3),
                                   (256, 2), (96, 1)])
def test_pad_psum_tiles_keeps_every_tile(k, n_p):
    """Lane padding of each PSUM tile's K slice: the padded K is a whole
    number of 128-lane tiles per PSUM tile, and Algorithm 1 on the padded
    codes equals Algorithm 1 on the originals (no K index changes tile)."""
    from repro.kernels.apsq_matmul.ops import LANE, pad_psum_tiles
    key = jax.random.PRNGKey(k + n_p)
    x = _codes(key, (2, 3, k))                  # leading (expert) axes too
    w = _codes(jax.random.fold_in(key, 1), (2, k, 16))
    xp, wp = pad_psum_tiles(x, w, n_p)
    kp = xp.shape[-1]
    assert wp.shape == (2, kp, 16) and kp % n_p == 0
    assert n_p == 1 or (kp // n_p) % LANE == 0
    for e in range(2):
        exps = choose_exps(x[e], w[e], n_p=n_p, gs=2)
        np.testing.assert_array_equal(
            np.asarray(apsq_matmul_ref(xp[e], wp[e], exps, n_p=n_p, gs=2)),
            np.asarray(apsq_matmul_ref(x[e], w[e], exps, n_p=n_p, gs=2)))


# ---------------------------------------------------------------------------
# m=1 decode fast path (single grid row, K reduction unrolled in-register)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n,n_p,gs", [
    (32, 16, 4, 2),    # tiny
    (45, 16, 4, 2),    # ragged K -> remainder PSUM group
    (64, 32, 8, 3),    # PSQ-ish tail inside a group
    (48, 16, 1, 1),    # n_p=1: single final tile
])
def test_m1_fastpath_bit_exact(k, n, n_p, gs):
    """block_m=1 takes the fast path (no bank scratch, no K grid steps);
    it must stay bit-identical to the oracle AND the generic grid."""
    key = jax.random.PRNGKey(k * 7 + n)
    x = _codes(key, (1, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    exps = choose_exps(x, w, n_p=n_p, gs=gs)
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    fast = apsq_matmul_int8(x, w, exps, gs=gs, block_m=1, interpret=True)
    generic = apsq_matmul_int8(x, w, exps, gs=gs, block_m=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fast))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(generic))


def test_m1_fastpath_per_column_exponents():
    """The fast path reads [n_p, N] banks whole — per-column shifts must
    match the broadcasting oracle."""
    key = jax.random.PRNGKey(29)
    k, n, n_p, gs = 64, 24, 4, 2
    x = _codes(key, (1, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    base = choose_exps(x, w, n_p=n_p, gs=gs)
    exps = base[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :] % 3
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    out = apsq_matmul_int8(x, w, exps, gs=gs, block_m=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_m1_default_resolution_takes_fastpath():
    """With blocks unset, M=1 resolves block_m=1 via the autotune
    heuristic — the decode shape must not pad to sublane rows."""
    from repro.kernels import autotune
    cfg = autotune.get_block_config(1, 64, 32, n_p=4, gs=2)
    assert cfg.block_m == 1
    key = jax.random.PRNGKey(31)
    x = _codes(key, (1, 64))
    w = _codes(jax.random.fold_in(key, 1), (64, 32))
    exps = choose_exps(x, w, n_p=4, gs=2)
    ref = apsq_matmul_ref(x, w, exps, n_p=4, gs=2)
    out = apsq_matmul_int8(x, w, exps, gs=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_full_exp_layout_matches_blocked():
    """exp_layout="full" (whole [n_p, N] bank resident, dynamic column
    slice per tile) == "blocked" == oracle."""
    key = jax.random.PRNGKey(37)
    m, k, n, n_p, gs = 8, 64, 32, 4, 2
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    base = choose_exps(x, w, n_p=n_p, gs=gs)
    exps = base[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :] % 2
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    for layout in ("blocked", "full"):
        out = apsq_matmul_int8(x, w, exps, gs=gs, block_m=8, block_n=16,
                               exp_layout=layout, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                      err_msg=f"exp_layout={layout}")


# ---------------------------------------------------------------------------
# Fused MoE expert grid (one pallas_call for all E experts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_experts", [1, 4, 8])
def test_expert_fused_bit_exact_vs_unrolled(n_experts):
    """ONE fused launch over the stacked [E, ...] bank == the E unrolled
    single-expert launches == the oracle, expert by expert."""
    from repro.kernels.apsq_matmul import apsq_expert_matmul_int8
    key = jax.random.PRNGKey(41 + n_experts)
    m, k, n, n_p, gs = 8, 32, 16, 4, 2
    x = _codes(key, (n_experts, m, k))
    w = _codes(jax.random.fold_in(key, 1), (n_experts, k, n))
    exps = jnp.stack([choose_exps(x[e], w[e], n_p=n_p, gs=gs)
                      for e in range(n_experts)])
    fused = apsq_expert_matmul_int8(x, w, exps, gs=gs, interpret=True)
    assert fused.shape == (n_experts, m, n)
    for e in range(n_experts):
        ref = apsq_matmul_ref(x[e], w[e], exps[e], n_p=n_p, gs=gs)
        single = apsq_matmul_int8(x[e], w[e], exps[e], gs=gs,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(fused[e]),
                                      err_msg=f"expert {e} vs oracle")
        np.testing.assert_array_equal(np.asarray(single),
                                      np.asarray(fused[e]),
                                      err_msg=f"expert {e} vs unrolled")


def test_expert_fused_ragged_k_and_per_column_banks():
    """Ragged K (remainder PSUM group) and [E, n_p, N] per-column banks
    through the fused grid."""
    from repro.kernels.apsq_matmul import apsq_expert_matmul_int8
    key = jax.random.PRNGKey(43)
    E, m, k, n, n_p, gs = 3, 8, 45, 16, 4, 2
    x = _codes(key, (E, m, k))
    w = _codes(jax.random.fold_in(key, 1), (E, k, n))
    base = jnp.stack([choose_exps(x[e], w[e], n_p=n_p, gs=gs)
                      for e in range(E)])
    exps = base[:, :, None] + jnp.arange(n, dtype=jnp.int32)[None, None] % 3
    out = apsq_expert_matmul_int8(x, w, exps, gs=gs, interpret=True)
    for e in range(E):
        ref = apsq_matmul_ref(x[e], w[e], exps[e], n_p=n_p, gs=gs)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out[e]),
                                      err_msg=f"expert {e}")


def test_expert_fused_baseline_w8a8():
    """The fused INT32-accumulator baseline == per-expert integer matmul."""
    from repro.kernels.apsq_matmul import baseline_expert_matmul_int8
    key = jax.random.PRNGKey(47)
    E, m, k, n = 2, 8, 32, 16
    x = _codes(key, (E, m, k))
    w = _codes(jax.random.fold_in(key, 1), (E, k, n))
    out = baseline_expert_matmul_int8(x, w, interpret=True)
    for e in range(E):
        np.testing.assert_array_equal(
            np.asarray(baseline_matmul_ref(x[e], w[e])), np.asarray(out[e]))
