"""Serving benchmark: continuous batching under Poisson load.

Drives the ``PagedServingEngine`` (INT8 paged KV cache, scheduler with
admission/eviction, prefill bucketing) with a synthetic open-loop load:
request arrivals are a Poisson process over decode steps, prompt and
output lengths are mixed, and every stream decodes greedily.  Reported:

  * tokens/s (aggregate decode throughput across all streams),
  * p50/p99 per-token latency (wall-clock of the engine step that
    produced each token — a fused macro-step's wall is attributed to
    every token it drained) and p50/p99 time-to-first-token,
  * the engine's dispatch counters per load cell: wall time, prefill and
    decode dispatches, dispatches per token and the macro-step scan-
    length histogram (``host_breakdown``),
  * a ``--decode-horizon`` sweep section (``horizon_sweep`` records):
    a saturated decode-bound cell — every stream generates the same
    fixed token count (a multiple of every swept horizon) and all
    requests queue upfront, so scan-lane waste is structurally zero —
    rerun across fused scan lengths {1, 4, 8, 16} (full) or {1, 8}
    (smoke).  The runs differ only in dispatch granularity: the clean
    before/after of moving the decode loop on device, which CI gates
    via ``check_serving_floor.py --min-horizon-speedup``.  (The Poisson
    cells keep measuring admission churn, where short streams favour
    small horizons — see the engine docstring's guidance.)
  * scheduler counters (admissions, preemptions) under the page pool,
  * KV-cache bytes: paged INT8 pools vs the dense f32 / native-dtype
    caches the ``ServingEngine`` baseline would allocate.

Before generating load the bench runs the parity gate the CI ``serve``
job rides on: greedy outputs of the batched engine must be
token-identical to the single-stream engine (same pools, batch 1), and
the oracle and interpret-mode Pallas backends must agree token-for-token
through the ``kv_attention`` exec op family.  A parity failure is a
hard error — throughput numbers from a wrong engine are worthless.

``--smoke`` (the CI job) runs 64 concurrent streams on the smoke
tinyllama config; the full run drives hundreds of streams.  ``--json
BENCH_serving.json`` emits machine-readable records so the serving
trajectory is tracked across PRs like ``BENCH_kernel.json``.
"""
import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.exec import PallasBackend
from repro.kernels import autotune
from repro.models.model import init_lm
from repro.serving import PagedServingEngine, Request, paged_cache_bytes


def _engine(params, cfg, *, max_batch, n_pages, backend="auto",
            page_size=16, prefill_chunk=16, max_pages_per_slot=None,
            decode_horizon=8):
    return PagedServingEngine(
        params, cfg, max_batch=max_batch, page_size=page_size,
        n_pages=n_pages, prefill_chunk=prefill_chunk, backend=backend,
        max_pages_per_slot=max_pages_per_slot,
        decode_horizon=decode_horizon)


def _requests(cfg, n_streams, rng, *, max_new_lo=4, max_new_hi=12,
              prompt_lo=4, prompt_hi=14):
    reqs = []
    for i in range(n_streams):
        L = int(rng.integers(prompt_lo, prompt_hi))
        reqs.append(Request(
            uid=i, tokens=rng.integers(0, cfg.vocab, L).astype(np.int32),
            max_new_tokens=int(rng.integers(max_new_lo, max_new_hi))))
    return reqs


# ---------------------------------------------------------------------------
# Parity gate (what CI's `serve` target asserts before trusting numbers)
# ---------------------------------------------------------------------------

def run_parity(params, cfg, print_fn=print, records: list | None = None):
    """Batched == single-stream, oracle == pallas, and the fused decode
    horizon == per-token heartbeats — all token-for-token."""
    rng = np.random.default_rng(7)
    probes = _requests(cfg, 4, rng, max_new_lo=6, max_new_hi=7)

    def outs(max_batch, backend, decode_horizon=8):
        eng = _engine(params, cfg, max_batch=max_batch, n_pages=48,
                      backend=backend, decode_horizon=decode_horizon)
        done = eng.run([Request(uid=r.uid, tokens=r.tokens,
                                max_new_tokens=r.max_new_tokens)
                        for r in probes])
        return {r.uid: r.out for r in done}

    single = outs(1, "oracle")
    batched = outs(4, "oracle")
    pallas = outs(4, PallasBackend(interpret=True))
    stepwise = outs(4, "oracle", decode_horizon=1)
    batch_ok = batched == single
    backend_ok = pallas == batched
    horizon_ok = stepwise == batched
    print_fn(f"serving,parity,batched_eq_single={batch_ok},"
             f"pallas_eq_oracle={backend_ok},"
             f"horizon_eq_stepwise={horizon_ok}")
    if records is not None:
        records.append({"section": "parity", "streams": len(probes),
                        "batched_eq_single": batch_ok,
                        "pallas_eq_oracle": backend_ok,
                        "horizon_eq_stepwise": horizon_ok})
    assert batch_ok, "batched paged engine diverged from single-stream"
    assert backend_ok, "pallas kv_attention diverged from oracle"
    assert horizon_ok, "fused decode horizon diverged from per-token steps"
    return batch_ok and backend_ok and horizon_ok


# ---------------------------------------------------------------------------
# Load generator
# ---------------------------------------------------------------------------

def run_load(params, cfg, *, n_streams, max_batch, arrival_rate,
             seed=0, print_fn=print, records: list | None = None,
             backend="auto", decode_horizon=8, section="load"):
    """Open-loop Poisson load: ``arrival_rate`` requests per decode step."""
    rng = np.random.default_rng(seed)
    reqs = _requests(cfg, n_streams, rng)
    inter = rng.exponential(1.0 / arrival_rate, n_streams)
    arrival_step = np.floor(np.cumsum(inter)).astype(int)

    page_size = 16
    # Pool sized so a full batch fits without thrashing but eviction is
    # still reachable under bursts.
    per_slot = -(-(14 + 12 + 1) // page_size) + 1
    n_pages = max_batch * per_slot + 1
    # Bound the page table to the workload footprint: the engine default
    # (n_pages - 1 columns) makes every decode gather/attend over the
    # whole pool — hundreds of dead positions per live token.
    eng = _engine(params, cfg, max_batch=max_batch, n_pages=n_pages,
                  backend=backend, page_size=page_size,
                  max_pages_per_slot=per_slot,
                  decode_horizon=decode_horizon)

    # Warm the compiles (pow2 prefill chunk shapes + every pow2 scan
    # length the horizon can shrink to) so the latency percentiles
    # measure steady-state serving, not tracing.
    h = 1
    while h <= decode_horizon:
        eng.run([Request(uid=-1, tokens=np.zeros(15, np.int32),
                         max_new_tokens=h + 1)])
        h *= 2
    eng.reset_counters()

    pending = sorted(zip(arrival_step, reqs), key=lambda x: x[0])
    arrive_t: dict = {}
    ttft: dict = {}
    tok_lat: list = []
    step = 0
    n_done = 0
    t_start = time.perf_counter()
    while pending or eng.sched.waiting or any(
            s is not None for s in eng.sched.slots):
        while pending and pending[0][0] <= step:
            _, r = pending.pop(0)
            arrive_t[r.uid] = time.perf_counter()
            eng.add_request(r)
        before = {r.uid: len(r.out) for r in reqs}
        t0 = time.perf_counter()
        n_done += len(eng.step())
        dt = time.perf_counter() - t0
        for r in reqs:
            new = len(r.out) - before[r.uid]
            if new and r.uid not in ttft and before[r.uid] == 0:
                ttft[r.uid] = time.perf_counter() - arrive_t[r.uid]
            tok_lat.extend([dt] * new)
        step += 1
    wall = time.perf_counter() - t_start
    eng.sched.assert_invariants()

    total_tokens = sum(len(r.out) for r in reqs)
    assert n_done == n_streams
    lat_ms = np.asarray(tok_lat) * 1e3
    ttft_ms = np.asarray(list(ttft.values())) * 1e3
    bytes_ = paged_cache_bytes(cfg, n_pages=n_pages, page_size=page_size,
                               max_batch=max_batch,
                               cache_len=per_slot * page_size)
    stats = eng.sched.stats
    dispatches = eng.decode_dispatches + eng.prefill_dispatches
    print_fn(
        f"serving,{section},streams={n_streams},max_batch={max_batch},"
        f"decode_horizon={decode_horizon},"
        f"steps={step},tokens={total_tokens},"
        f"tokens_per_s={total_tokens / wall:.1f},"
        f"p50_ms={np.percentile(lat_ms, 50):.1f},"
        f"p99_ms={np.percentile(lat_ms, 99):.1f},"
        f"ttft_p50_ms={np.percentile(ttft_ms, 50):.1f},"
        f"ttft_p99_ms={np.percentile(ttft_ms, 99):.1f},"
        f"admitted={stats.admitted},preempted={stats.preempted}")
    print_fn(
        f"serving,{section}_host,"
        f"wall_s={wall:.3f},dispatches={dispatches},"
        f"dispatches_per_token={dispatches / max(total_tokens, 1):.3f},"
        f"device_steps={eng.decode_device_steps}")
    print_fn(
        f"serving,kv_bytes,int8_paged={bytes_['int8_paged']:.3e},"
        f"dense_f32={bytes_['dense_f32']:.3e},"
        f"ratio={bytes_['int8_paged'] / bytes_['dense_f32']:.3f}")
    rec = {
        "section": section, "streams": n_streams,
        "max_batch": max_batch, "arrival_rate": arrival_rate,
        "decode_horizon": decode_horizon,
        "pages_per_slot": per_slot,
        "steps": step, "tokens": total_tokens,
        "tokens_per_s": round(total_tokens / wall, 1),
        "prefill_tokens": int(eng.prefill_tokens),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "ttft_p50_ms": round(float(np.percentile(ttft_ms, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(ttft_ms, 99)), 2),
        "admitted": stats.admitted, "preempted": stats.preempted,
        "host_breakdown": {
            "wall_s": round(wall, 4),
            "decode_dispatches": eng.decode_dispatches,
            "prefill_dispatches": eng.prefill_dispatches,
            "dispatches_per_token": round(
                dispatches / max(total_tokens, 1), 4),
            "device_steps": eng.decode_device_steps,
            "horizon_hist": {str(k): v
                             for k, v in sorted(eng.horizon_hist.items())},
        },
        "kv_bytes": bytes_}
    if records is not None:
        records.append(rec)
    return rec


def run_horizon_sweep(params, cfg, *, n_streams, max_batch, horizons,
                      seed=0, print_fn=print, records: list | None = None,
                      backend="auto", max_new=48, prompt_len=12):
    """Saturated decode-bound cell across fused scan lengths.

    Every stream generates exactly ``max_new`` tokens (a multiple of
    every swept horizon, so macro-steps never straddle a request's
    tail) and all requests are queued upfront, keeping the batch full
    for the whole run: scan-lane waste is structurally zero and the
    horizons differ only in dispatch granularity.  This isolates the
    decode-loop fusion economics the ``--min-horizon-speedup`` CI gate
    rides on; the Poisson ``load`` cells keep measuring admission
    churn, where 4-12-token streams legitimately favour ``h == 1``.
    """
    rng = np.random.default_rng(seed)
    page_size = 16
    per_slot = -(-(prompt_len + max_new + 1) // page_size) + 1
    n_pages = max_batch * per_slot + 1
    for h in horizons:
        eng = _engine(params, cfg, max_batch=max_batch, n_pages=n_pages,
                      backend=backend, page_size=page_size,
                      max_pages_per_slot=per_slot, decode_horizon=h)
        k = 1
        while k <= h:
            eng.run([Request(uid=-1, tokens=np.zeros(prompt_len, np.int32),
                             max_new_tokens=k + 1)])
            k *= 2
        eng.reset_counters()
        reqs = [Request(uid=i,
                        tokens=rng.integers(0, cfg.vocab, prompt_len)
                        .astype(np.int32),
                        max_new_tokens=max_new)
                for i in range(n_streams)]
        t0 = time.perf_counter()
        done = eng.run(reqs)
        wall = time.perf_counter() - t0
        eng.sched.assert_invariants()
        total_tokens = sum(len(r.out) for r in done)
        assert total_tokens == n_streams * max_new
        dispatches = eng.decode_dispatches + eng.prefill_dispatches
        print_fn(
            f"serving,horizon_sweep,streams={n_streams},"
            f"max_batch={max_batch},decode_horizon={h},"
            f"max_new={max_new},tokens={total_tokens},"
            f"tokens_per_s={total_tokens / wall:.1f},"
            f"dispatches={dispatches},"
            f"device_steps={eng.decode_device_steps}")
        if records is not None:
            records.append({
                "section": "horizon_sweep", "streams": n_streams,
                "max_batch": max_batch, "decode_horizon": h,
                "max_new": max_new, "pages_per_slot": per_slot,
                "tokens": total_tokens,
                "tokens_per_s": round(total_tokens / wall, 1),
                "host_breakdown": {
                    "wall_s": round(wall, 4),
                    "decode_dispatches": eng.decode_dispatches,
                    "prefill_dispatches": eng.prefill_dispatches,
                    "dispatches_per_token": round(
                        dispatches / max(total_tokens, 1), 4),
                    "device_steps": eng.decode_device_steps,
                    "horizon_hist": {
                        str(k): v
                        for k, v in sorted(eng.horizon_hist.items())},
                }})


def run_kernel_blocks(print_fn=print, records: list | None = None):
    """Record the launch geometry the serving engines' Pallas GEMMs will
    resolve per shape class — so a BENCH_serving.json diff shows when a
    tuned cache (or a heuristic change) moved the serving block shapes."""
    table = autotune.resolved_table()
    for cls, cfg in table.items():
        print_fn(f"serving,kernel_blocks,{cls},bm={cfg['block_m']},"
                 f"bn={cfg['block_n']},{cfg['exp_layout']},"
                 f"{cfg['blocks_source']}")
    if records is not None:
        records.append({"section": "kernel_blocks", **table})


def run(print_fn=print, smoke: bool = False, records: list | None = None,
        seed: int = 0, decode_horizon: int = 8):
    cfg = get_smoke("tinyllama-1.1b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    run_kernel_blocks(print_fn, records)
    run_parity(params, cfg, print_fn, records)
    # The --decode-horizon sweep runs its own saturated decode-bound
    # cell (fixed-length streams, arrivals upfront) — the Poisson load
    # cells below keep measuring admission churn.
    if smoke:  # the CI cell: 64 concurrent streams, oracle numbers
        sweep_cell, horizons = (128, 32), (1, decode_horizon)
        cells = ((64, 64),)
    else:  # the CI cell first (so the committed floor overlaps smoke
           # runs and check_serving_floor can gate them), then hundreds
           # of streams at two concurrency points
        sweep_cell, horizons = (128, 32), (1, 4, 8, 16)
        cells = ((64, 64), (128, 32), (256, 64))
    for n_streams, max_batch in cells:
        run_load(params, cfg, n_streams=n_streams,
                 max_batch=max_batch, arrival_rate=8.0, seed=seed,
                 print_fn=print_fn, records=records,
                 decode_horizon=decode_horizon)
    run_horizon_sweep(params, cfg, n_streams=sweep_cell[0],
                      max_batch=sweep_cell[1],
                      horizons=tuple(dict.fromkeys(horizons)), seed=seed,
                      print_fn=print_fn, records=records)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="parity gate + one 64-stream load cell (CI job)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write machine-readable records "
                         "(e.g. BENCH_serving.json)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="fused decode steps per engine heartbeat for the "
                         "load cells (pow2; the sweep section always "
                         "includes horizon 1 for the speedup baseline)")
    args = ap.parse_args(argv)
    records: list | None = [] if args.json else None
    run(smoke=args.smoke, records=records, seed=args.seed,
        decode_horizon=args.decode_horizon)
    if args.json:
        payload = {
            "benchmark": "serving_bench",
            "smoke": bool(args.smoke),
            "unix_time": int(time.time()),
            "jax_version": jax.__version__,
            "jax_backend": jax.default_backend(),
            "platform": platform.platform(),
            "records": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"serving,json -> {args.json} ({len(records)} records)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
