"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``.

Spins up a continuous-batching engine on a reduced (default) or, with
``--full``, the published config and drives a synthetic request stream,
reporting per-request outputs and decode-step throughput.  ``--engine
paged`` serves through the paged INT8 KV cache (``PagedServingEngine``:
page-pool scheduler with mid-decode eviction, attention reads via the
``kv_attention`` exec op family); the default ``dense`` engine keeps the
float reference path.

Full width on a TPU, through the compiled Pallas kernels::

    python -m repro.launch.serve --arch tinyllama-1.1b --full \\
        --engine paged --exported --backend pallas

``--backend pallas`` never falls back: without a TPU it fails.  Compiled
programs persist in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else
in ``.jax_cache/`` at the root of the checkout.

``build_model`` and ``build_engine`` are the construction code shared
with ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/serve.py.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache lives at the fixed ``CACHE_DIR``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def build_model(arch: str, *, full: bool = False, exported: bool = False,
                seed: int = 0):
    """``(cfg, params)`` for ``arch``: the published config with ``full``,
    else its smoke config; weights from ``seed``.  ``exported`` gives the
    config the paper's APSQ preset (gs=2, n_p=4) when it ships without
    quantization, and calibrates the params for ``export_quantized``."""
    from repro.configs import get_config, get_smoke
    from repro.models.model import init_lm

    cfg = get_config(arch) if full else get_smoke(arch)
    if exported and (cfg.quant is None or not cfg.quant.enabled):
        from repro.core import QuantConfig
        cfg = cfg.with_quant(QuantConfig.apsq(gs=2, n_p=4))
        print(f"[serve] {arch} has quant disabled -> "
              f"applying apsq(gs=2, n_p=4) for --exported")
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    if exported:
        from repro.quant import calibrate_model
        tok = np.random.default_rng(seed).integers(0, cfg.vocab, size=(2, 32))
        params = calibrate_model(params, cfg, {"tokens": jax.numpy.asarray(
            tok, jax.numpy.int32)})
    return cfg, params


def build_engine(params, cfg, *, engine: str = "paged",
                 exported: bool = False, backend="auto", mesh=None,
                 wire: str = "int8", max_batch: int = 4,
                 cache_len: int = 256, page_size: int = 16,
                 decode_horizon: int = 8):
    """A serving engine over ``params``: ``paged`` (INT8 page pools sized
    for ``max_batch`` slots of ``cache_len`` tokens) or ``dense``;
    ``exported`` exports the calibrated params to INT8 codes first."""
    from repro.serving import PagedServingEngine, ServingEngine

    if engine == "paged":
        n_pages = cache_len // page_size * max_batch + 1
        kw = dict(max_batch=max_batch, page_size=page_size,
                  n_pages=n_pages, backend=backend, mesh=mesh, wire=wire,
                  decode_horizon=decode_horizon)
        return (PagedServingEngine.from_exported(params, cfg, **kw)
                if exported else PagedServingEngine(params, cfg, **kw))
    kw = dict(max_batch=max_batch, cache_len=cache_len, backend=backend)
    return (ServingEngine.from_exported(params, cfg, **kw) if exported
            else ServingEngine(params, cfg, **kw))


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (default: the "
                         "arch's reduced smoke config)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--engine", choices=("dense", "paged"), default="dense",
                    help="dense float KV slots, or the paged INT8 KV "
                         "cache with the continuous-batching scheduler")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="fused decode steps per engine heartbeat (pow2). "
                         "Raise when decode is dispatch-bound; 1 restores "
                         "the per-token heartbeat (tight page pools, "
                         "strict per-token SLO).  Paged engine only.")
    ap.add_argument("--backend", default="auto",
                    help="exec backend for integer ops: pallas (the "
                         "compiled kernels; fails without a TPU) | oracle "
                         "| auto (pallas on TPU, oracle elsewhere)")
    ap.add_argument("--mesh", default=None, metavar="SHAPE",
                    help="serve across a device mesh, e.g. '1x2' "
                         "(data x model) or '2x1x2' (pod x data x model). "
                         "Implies --engine paged --exported; the model "
                         "axis shards INT8 code banks + KV head pools "
                         "(repro.dist.tp).  Off-TPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first.")
    ap.add_argument("--wire", choices=("int8", "fp32"), default="int8",
                    help="collective payload for sharded serving: int8 "
                         "codes (default) or the fp32 parity-debug path")
    ap.add_argument("--exported", action="store_true",
                    help="calibrate + export to INT8 codes and serve "
                         "through the integer kernel path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    if get_config(args.arch).encdec:
        raise SystemExit("enc-dec serving requires encoder inputs; use the "
                         "examples/serve.py driver for seamless")
    enable_compile_cache()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_smoke_mesh
        shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        axes = (("pod", "data", "model") if len(shape) == 3
                else ("data", "model"))
        mesh = make_smoke_mesh(shape, axes)
        args.engine = "paged"
        args.exported = True
        print(f"[serve] mesh {dict(mesh.shape)} wire={args.wire}")

    cfg, params = build_model(args.arch, full=args.full,
                              exported=args.exported, seed=args.seed)

    from repro.serving import Request
    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        size=rng.integers(4, 32)),
                    max_new_tokens=args.max_new_tokens)
            for i in range(args.requests)]

    engine = build_engine(params, cfg, engine=args.engine,
                          exported=args.exported, backend=args.backend,
                          mesh=mesh, wire=args.wire,
                          max_batch=args.max_batch, cache_len=args.cache_len,
                          page_size=args.page_size,
                          decode_horizon=args.decode_horizon)
    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, wall-clock incl. compilation)")
    for r in done[:4]:
        print(f"  req {r.uid}: prompt[{len(r.tokens)}] -> {r.out}")


if __name__ == "__main__":
    main()
