"""Kernel benchmark (§III-C): APSQ Pallas kernel vs references.

On this CPU container the kernel runs in interpret mode, so wall-clock is
not a TPU signal; what we measure and report:
  * bit-exactness vs the integer oracle across a shape sweep (including
    ragged K and per-column exponent layouts),
  * oracle-vs-pallas *backend* parity + throughput side by side on one
    exported layer, at the serving shapes that matter (decode M=1,
    batched prefill) — the ``repro.exec`` path ``ServingEngine`` runs.
    Every backend record names the (block_m, block_n, exp_layout) the
    Pallas launch used and whether it came from the autotune cache or
    the heuristic, so tuned and default runs are distinguishable in
    ``BENCH_kernel.json``,
  * the m=1 decode fast path vs the generic grid (regression record for
    the single-token launch geometry),
  * the fused MoE expert grid (one pallas_call for all E experts) vs the
    per-expert unrolled launches it replaced,
  * accumulator traffic (bytes) of APSQ banks vs the INT32 baseline —
    the quantity the paper's energy claim rides on (beta 4 -> 1),
  * throughput of the jitted *fake-quant* APSQ GEMM vs plain GEMM on CPU
    (QAT-time overhead of the technique).

``--smoke`` (the CI kernel-backend job) runs the correctness sweep and
the backend parity + fast-path sections only, at reduced shapes.  Full
runs also measure the smoke shapes, so a CI smoke run can always be
floor-checked against the checked-in full-run records
(``benchmarks/check_kernel_floor.py``).

``--tune`` runs the block autotuner (``repro.kernels.autotune``) over
the benchmark shape classes first — winners land in the on-disk cache
and the backend records' ``blocks_source`` flips to "tuned".

``--json BENCH_kernel.json`` additionally emits every measurement as a
machine-readable record (throughput + parity per shape, plus jax/backend
metadata) so the perf trajectory is tracked across PRs instead of living
only in CI logs.
"""
import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import QuantConfig, quant_dense, quant_params_init, \
    calibrate_dense
from repro.exec import backend_parity_check
from repro.kernels import autotune
from repro.kernels.apsq_matmul import (
    accumulator_vmem_bytes,
    apsq_expert_matmul_int8,
    apsq_matmul_int8,
    apsq_matmul_ref,
    choose_exps,
)
from repro.quant import export_quantized

from .common import timed


def run_correctness(print_fn=print, records: list | None = None):
    key = jax.random.PRNGKey(0)
    cells = [(32, 128, 64, 8, 2), (64, 256, 128, 4, 4),
             (16, 64, 32, 8, 1), (128, 512, 128, 16, 3),
             (8, 100, 32, 8, 2),   # ragged K -> remainder PSUM group
             (1, 192, 64, 6, 2)]   # decode shape M=1
    ok = 0
    for (m, k, n, n_p, gs) in cells:
        x = jax.random.randint(key, (m, k), -128, 128, jnp.int8)
        w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128,
                               128, jnp.int8)
        exps = choose_exps(x, w, n_p=n_p, gs=gs)
        ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
        out = apsq_matmul_int8(x, w, exps, gs=gs, interpret=True)
        equal = bool(np.array_equal(np.asarray(ref), np.asarray(out)))
        assert equal
        ok += 1
        if records is not None:
            records.append({"section": "correctness", "m": m, "k": k,
                            "n": n, "n_p": n_p, "gs": gs,
                            "bit_exact": equal})
    print_fn(f"kernel,bit_exact_cells={ok}/{len(cells)}")
    return ok


def _backend_cells(smoke: bool):
    """(shape_name, m, k, n) cells.  The small cells always run — they are
    what CI's smoke job measures, so full runs must include them for the
    floor gate to have matching (shape, m, k, n) records to compare."""
    cells = [("decode_m1", 1, 256, 128), ("prefill", 32, 256, 128)]
    if not smoke:
        cells += [("decode_m1", 1, 1024, 512), ("prefill", 256, 1024, 512)]
    return cells


def run_backends(print_fn=print, smoke: bool = False,
                 records: list | None = None):
    """Oracle vs Pallas backend on exported layers, side by side.

    Builds the full calibrate -> export artifact (per-channel weight
    scales, so the kernel runs the [n_p, N] exponent layout) and times
    ``execute_gemm`` per backend at the decode (M=1) and prefill shapes.
    Each record carries the Pallas launch geometry actually used.
    """
    gs, n_p = 2, 8
    key = jax.random.PRNGKey(1)
    deployed = {}
    all_equal = True
    for shape_name, m, k, n in _backend_cells(smoke):
        if (k, n) not in deployed:
            xcal = jax.random.normal(key, (max(32, m), k))
            w = jax.random.normal(jax.random.fold_in(key, 2),
                                  (k, n)) * 0.05
            cfg = QuantConfig.apsq(gs=gs, n_p=n_p)
            qp = calibrate_dense(quant_params_init(w, cfg, name="lin"),
                                 xcal, w)
            dep, _ = export_quantized({"lin": {"w": w, "qp": qp}})
            deployed[(k, n)] = dep["lin"]["qp"]
        dq = deployed[(k, n)]
        x = jax.random.normal(jax.random.fold_in(key, m), (m, k))
        _, times, equal = backend_parity_check(
            dq, x, reps=2 if smoke else 5, warmup=1 if smoke else 2)
        all_equal &= equal
        blocks = autotune.get_block_config(m, k, n, n_p=n_p, gs=gs)
        print_fn(f"kernel,backend,{shape_name},M={m},K={k},N={n},"
                 f"oracle_us={times['oracle']:.0f},"
                 f"pallas_us={times['pallas']:.0f},"
                 f"bm={blocks.block_m},bn={blocks.block_n},"
                 f"{blocks.source},bit_equal={equal}")
        if records is not None:
            macs = m * k * n
            records.append({
                "section": "backend", "shape": shape_name,
                "m": m, "k": k, "n": n, "gs": gs, "n_p": n_p,
                "bit_equal": bool(equal),
                **blocks.as_record(),
                **{f"{b}_us": round(t, 1) for b, t in times.items()},
                **{f"{b}_gmacs_per_s": round(macs / t / 1e3, 3)
                   for b, t in times.items() if t > 0}})
    assert all_equal, "oracle and pallas backends disagree"
    return all_equal


def _time_eager(f, *args, reps=3, **kw):
    """Wall-clock a jitted callable (compile + warmup excluded), us."""
    jax.block_until_ready(f(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run_m1_fastpath(print_fn=print, smoke: bool = False,
                    records: list | None = None):
    """Decode regression record: the m=1 fast path (block_m=1, K unrolled
    in one grid row) vs the generic grid at the same shape — bit parity
    gates, the timing ratio is the record."""
    k, n = (256, 128) if smoke else (1024, 512)
    n_p, gs = 8, 2
    key = jax.random.PRNGKey(3)
    x = jax.random.randint(key, (1, k), -128, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128,
                           jnp.int8)
    exps = choose_exps(x, w, n_p=n_p, gs=gs)
    ref = apsq_matmul_ref(x, w, exps, n_p=n_p, gs=gs)
    fast = lambda: apsq_matmul_int8(x, w, exps, gs=gs, block_m=1,
                                    interpret=True)
    generic = lambda: apsq_matmul_int8(x, w, exps, gs=gs, block_m=8,
                                       interpret=True)
    equal = bool(np.array_equal(np.asarray(ref), np.asarray(fast()))
                 and np.array_equal(np.asarray(ref), np.asarray(generic())))
    assert equal, "m=1 fast path disagrees with the oracle/generic grid"
    reps = 2 if smoke else 5
    t_fast = _time_eager(fast, reps=reps)
    t_gen = _time_eager(generic, reps=reps)
    print_fn(f"kernel,m1_fastpath,K={k},N={n},fast_us={t_fast:.0f},"
             f"generic_us={t_gen:.0f},x{t_gen / t_fast:.1f},"
             f"bit_exact={equal}")
    if records is not None:
        records.append({"section": "m1_fastpath", "m": 1, "k": k, "n": n,
                        "n_p": n_p, "gs": gs, "bit_exact": equal,
                        "fastpath_us": round(t_fast, 1),
                        "generic_us": round(t_gen, 1)})
    return equal


def run_expert_fused(print_fn=print, smoke: bool = False,
                     records: list | None = None):
    """Fused expert grid: ONE pallas_call for all E experts vs the E
    unrolled launches it replaced.  Parity gates against the per-expert
    oracle; the timing pair records the fusion win."""
    E = 4
    m, k, n = (16, 128, 64) if smoke else (64, 512, 256)
    n_p, gs = 8, 2
    key = jax.random.PRNGKey(4)
    x = jax.random.randint(key, (E, m, k), -128, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (E, k, n), -128,
                           128, jnp.int8)
    exps = jnp.stack([choose_exps(x[e], w[e], n_p=n_p, gs=gs)
                      for e in range(E)])
    fused = lambda: apsq_expert_matmul_int8(x, w, exps, gs=gs,
                                            interpret=True)
    unrolled = lambda: jnp.stack([
        apsq_matmul_int8(x[e], w[e], exps[e], gs=gs, interpret=True)
        for e in range(E)])
    out = fused()
    equal = all(
        np.array_equal(
            np.asarray(apsq_matmul_ref(x[e], w[e], exps[e], n_p=n_p,
                                       gs=gs)),
            np.asarray(out[e]))
        for e in range(E))
    assert equal, "fused expert grid disagrees with the per-expert oracle"
    reps = 2 if smoke else 5
    t_fused = _time_eager(fused, reps=reps)
    t_unrolled = _time_eager(unrolled, reps=reps)
    blocks = autotune.get_block_config(m, k, n, n_p=n_p, gs=gs,
                                       expert=True)
    print_fn(f"kernel,expert_fused,E={E},M={m},K={k},N={n},"
             f"fused_us={t_fused:.0f},unrolled_us={t_unrolled:.0f},"
             f"x{t_unrolled / t_fused:.1f},bit_exact={equal}")
    if records is not None:
        records.append({"section": "expert_fused", "n_experts": E,
                        "m": m, "k": k, "n": n, "n_p": n_p, "gs": gs,
                        "bit_exact": equal, **blocks.as_record(),
                        "fused_us": round(t_fused, 1),
                        "unrolled_us": round(t_unrolled, 1)})
    return equal


def run(print_fn=print, smoke: bool = False, records: list | None = None):
    key = jax.random.PRNGKey(0)
    # 1. correctness sweep (interpret mode)
    ok = run_correctness(print_fn, records)

    # 2. execution-backend parity + throughput (the serving path)
    run_backends(print_fn, smoke=smoke, records=records)

    # 3. decode fast-path + fused-expert regression records
    run_m1_fastpath(print_fn, smoke=smoke, records=records)

    if smoke:
        return ok

    run_expert_fused(print_fn, smoke=smoke, records=records)

    # 4. accumulator bytes: the beta 4->1 story per output tile
    for gs in (1, 2, 4):
        v = accumulator_vmem_bytes(128, 128, gs)
        print_fn(f"kernel,accumulator_bytes,gs={gs},"
                 f"apsq={v['apsq_banks']},int32={v['baseline_int32']},"
                 f"saving={1 - v['apsq_banks'] / v['baseline_int32']:.2f}")
        if records is not None:
            records.append({"section": "accumulator_bytes", "gs": gs,
                            "apsq_banks": v["apsq_banks"],
                            "baseline_int32": v["baseline_int32"]})

    # 5. QAT-time overhead of fake-quant APSQ vs plain matmul (CPU)
    xf = jax.random.normal(key, (256, 1024))
    wf = jax.random.normal(jax.random.fold_in(key, 2), (1024, 512)) * 0.05
    cfg = QuantConfig.apsq(gs=2, n_p=8)
    qp = calibrate_dense(quant_params_init(wf, cfg), xf, wf, cfg)

    plain = jax.jit(lambda a, b: a @ b)
    apsq = jax.jit(lambda a, b: quant_dense(a, b, qp, cfg))
    t0, _ = timed(plain, xf, wf)
    t1, y = timed(apsq, xf, wf)
    rel = float(jnp.mean(jnp.abs(y - xf @ wf)) /
                jnp.mean(jnp.abs(xf @ wf)))
    print_fn(f"kernel,qat_overhead,plain_us={t0:.0f},apsq_us={t1:.0f},"
             f"x{t1 / t0:.1f},rel_err={rel:.4f}")
    if records is not None:
        records.append({"section": "qat_overhead", "plain_us": round(t0),
                        "apsq_us": round(t1), "rel_err": rel})

    # 6. INT8 KV-cache decode attention (second kernel): accuracy vs fp32
    #    reference + the bandwidth story (decode cells are HBM-bound).
    from repro.kernels.int8_kv_attention import (
        cache_bytes, fp_attention_ref, int8_kv_attention_f32)
    q = jax.random.normal(key, (2, 8, 64))
    kv = jax.random.normal(jax.random.fold_in(key, 3), (2, 256, 2, 64))
    vv = jax.random.normal(jax.random.fold_in(key, 4), (2, 256, 2, 64))
    L = jnp.full((2,), 256, jnp.int32)
    fp = fp_attention_ref(q, kv, vv, L)
    out = int8_kv_attention_f32(q, kv, vv, L, block_s=128, interpret=True)
    rel = float(jnp.mean(jnp.abs(out - fp)) / jnp.mean(jnp.abs(fp)))
    cb = cache_bytes(128, 32768, 4, 128)  # tinyllama decode_32k cell
    print_fn(f"kernel,int8_kv_attention,rel_err_vs_fp32={rel:.4f},"
             f"decode32k_cache_bytes: bf16={cb['bf16']:.2e} -> "
             f"int8={cb['int8']:.2e} ({cb['int8'] / cb['bf16']:.2f}x)")
    if records is not None:
        records.append({"section": "int8_kv_attention",
                        "rel_err_vs_fp32": rel,
                        "decode32k_cache_bytes": cb})
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="correctness + backend parity only (CI job)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write machine-readable records "
                         "(e.g. BENCH_kernel.json)")
    ap.add_argument("--tune", action="store_true",
                    help="run the block autotuner over the benchmark "
                         "shape classes first (winners land in the "
                         "table $REPRO_AUTOTUNE_CACHE names; records flip "
                         "to blocks_source='tuned')")
    args = ap.parse_args(argv)
    if args.tune:
        autotune.tune_standard_shapes(verbose=True)
    records: list | None = [] if args.json else None
    run(smoke=args.smoke, records=records)
    if args.json:
        payload = {
            "benchmark": "kernel_bench",
            "smoke": bool(args.smoke),
            "unix_time": int(time.time()),
            "jax_version": jax.__version__,
            "jax_backend": jax.default_backend(),
            "platform": platform.platform(),
            "records": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"kernel,json -> {args.json} ({len(records)} records)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
