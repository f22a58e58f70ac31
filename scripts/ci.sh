#!/usr/bin/env bash
# CI entrypoint.
#
#   scripts/ci.sh                 tier-1: full test suite (extra args -> pytest)
#   scripts/ci.sh kernel-backend  interpret-mode kernel-backend job: the
#                                 kernel-vs-oracle parity grid + exec-backend
#                                 + block-autotuner tests + a kernel_bench
#                                 --smoke pass (with the machine-readable
#                                 BENCH_kernel.json so the perf trajectory
#                                 is tracked per run).  The fresh run is
#                                 then gated against the committed
#                                 BENCH_kernel.json throughput floor
#                                 (benchmarks/check_kernel_floor.py), so
#                                 both parity AND launch-geometry perf
#                                 regressions fail fast, in isolation from
#                                 the (slower) tier-1 run.
#   scripts/ci.sh search          policy-search smoke: 2-iteration (gs, n_p)
#                                 co-exploration on the tiny arch; fails
#                                 unless the Pareto front is non-empty with
#                                 a heterogeneous member and the winning
#                                 policy round-trips calibrate -> export ->
#                                 pallas with parity.
#   scripts/ci.sh dist            tensor/expert-parallel serving smoke:
#                                 plan/GEMM/engine parity tests over 2- and
#                                 8-way host-device meshes, then dist_bench
#                                 --smoke — sharded greedy decode gated
#                                 token-identical to single-device under
#                                 BOTH wire modes, and the switchable
#                                 int8-vs-fp32 collective byte ratio gated
#                                 >= 3.5x — before the 1->2->8 scaling
#                                 numbers land in BENCH_dist.json.
#   scripts/ci.sh serve           continuous-batching serving smoke: paged
#                                 INT8 KV cache tests + serving_bench
#                                 --smoke (64 Poisson streams, fused
#                                 decode_horizon=8 macro-steps, plus a
#                                 saturated 128-stream decode-bound
#                                 horizon {1,8} sweep cell).  The bench
#                                 itself gates on decode parity — batched
#                                 == single-stream, oracle == interpret-
#                                 mode pallas, AND fused horizon ==
#                                 per-token heartbeats, token-for-token —
#                                 before reporting tokens/s, p50/p99 and
#                                 the dispatch counters into
#                                 BENCH_serving.json.  The
#                                 fresh run is then gated against the
#                                 committed BENCH_serving.json tokens/s +
#                                 ttft_p50 floors AND its own h8-vs-h1
#                                 sweep ratio (check_serving_floor.py
#                                 --min-horizon-speedup), so a scheduler,
#                                 chunked-prefill, or decode-fusion
#                                 regression fails fast like a
#                                 kernel-geometry one.
#
# Collection regressions (missing modules, import errors) fail the run
# because pytest errors out before running a single test.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install --quiet -r requirements-dev.txt
python -m pip install --quiet "jax==0.9.0" numpy 2>/dev/null || true

if [[ "${1:-}" == "kernel-backend" ]]; then
    shift
    python -m pytest -q tests/test_kernels.py tests/test_exec.py \
        tests/test_autotune.py "$@"
    # Save the committed floor BEFORE the bench overwrites BENCH_kernel.json.
    floor="$(mktemp)"
    git show HEAD:BENCH_kernel.json > "$floor" 2>/dev/null || floor=""
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m benchmarks.kernel_bench --smoke --json BENCH_kernel.json
    if [[ -n "$floor" ]]; then
        PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
            python -m benchmarks.check_kernel_floor BENCH_kernel.json "$floor"
        rm -f "$floor"
    else
        echo "floor,WARN,no committed BENCH_kernel.json — floor gate skipped"
    fi
elif [[ "${1:-}" == "search" ]]; then
    shift
    python -m pytest -q tests/test_search.py "$@"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.search.cli --arch tinyllama-1.1b --budget-smoke
elif [[ "${1:-}" == "dist" ]]; then
    shift
    python -m pytest -q tests/test_dist_tp.py "$@"
    # dist_bench hard-gates internally (mesh-vs-single parity under both
    # wire modes, switchable byte ratio >= 3.5x) before writing the
    # record; the committed BENCH_dist.json is the tracked trajectory.
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m benchmarks.dist_bench --smoke --json BENCH_dist.json
elif [[ "${1:-}" == "serve" ]]; then
    shift
    python -m pytest -q tests/test_paged_serving.py tests/test_kernels_kv.py "$@"
    # Save the committed floor BEFORE the bench overwrites BENCH_serving.json.
    floor="$(mktemp)"
    git show HEAD:BENCH_serving.json > "$floor" 2>/dev/null || floor=""
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m benchmarks.serving_bench --smoke --decode-horizon 8 \
        --json BENCH_serving.json
    if [[ -n "$floor" ]]; then
        PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
            python -m benchmarks.check_serving_floor BENCH_serving.json \
            "$floor" --min-horizon-speedup 1.05
        rm -f "$floor"
    else
        echo "floor,WARN,no committed BENCH_serving.json — floor gate skipped"
    fi
else
    python -m pytest -x -q "$@"
fi
