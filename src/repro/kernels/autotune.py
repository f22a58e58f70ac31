"""Block-shape autotuner for the APSQ Pallas kernels.

The kernel's launch geometry — ``(block_m, block_n)`` tile sizes and the
exponent-block layout — is a per-shape decision, not a constant: decode
(M=1) wants one grid row with the whole K reduction inlined, prefill wants
the largest tiles VMEM can hold, and MoE expert GEMMs sit in between.
QUIDAM (PAPERS.md) treats exactly these tiling/PE-array parameters as a
searchable axis of the accelerator; this module applies the same idea at
the kernel level.

Three layers, fastest first:

  * ``get_block_config(m, k, n, ...)`` — the hot-path lookup every kernel
    launch goes through.  Never times anything: it consults the table of
    tuned winners named by ``$REPRO_AUTOTUNE_CACHE`` (none when unset)
    and falls back to the static heuristic, so interpret-mode CI and
    trace-time resolution stay deterministic.
  * ``heuristic_config`` — the static fallback: shape-class-aware tile
    sizes clamped to a VMEM budget.
  * ``tune`` / ``tune_standard_shapes`` — the measured search.  Runs each
    candidate config eagerly (``block_until_ready`` wall-clock), picks
    the fastest, and persists it in a versioned JSON table keyed by
    ``(shape class, n_p, gs, jax backend)``.  Only ever invoked
    explicitly (``kernel_bench --tune`` or the CLI below) — never from
    inside a jitted trace.

Shape classes
-------------
``decode_m1``  M == 1 — single-token decode, served by the m=1 fast-path
               kernel (one grid row over N, the K reduction unrolled).
``small_m``    1 < M <= 32 — small decode batches.
``prefill``    M > 32 — batched prefill / QAT forward.
``expert``     MoE expert-bank GEMMs (per-expert M = dispatch capacity),
               executed by the fused expert-grid kernel.
``prefill_attn``  chunked-prefill KV attention (``int8_kv_attention``
               with a [chunk] query block): m = chunk rows, k = head dim,
               n = gathered KV sequence; ``block_n`` is the kernel's
               ``block_s`` KV tile (snapped to a divisor of S at launch).

Cache
-----
The file ``$REPRO_AUTOTUNE_CACHE`` names, or a ``path=`` argument.  With
neither there is no table: every launch takes the heuristic, so block
choices never depend on state outside the checkout.  The file is
versioned and keyed by jax backend, so a CPU-tuned table never leaks
onto TPU.

CLI::

    PYTHONPATH=src python -m repro.kernels.autotune --cache t.json  # tune
    PYTHONPATH=src python -m repro.kernels.autotune --show          # table
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax

CACHE_VERSION = 1

SHAPE_CLASSES = ("decode_m1", "small_m", "prefill", "expert",
                 "prefill_attn")

# Exponent-block layouts for the [n_p, N] per-channel export layout:
#   "blocked" — the kernel sees a [n_p, block_n] VMEM slice per (j) tile
#               (re-fetched as j advances; minimal VMEM footprint),
#   "full"    — the whole [n_p, N] table sits in VMEM and the kernel
#               slices its column window dynamically (no re-fetch; costs
#               n_p * N bytes of VMEM — only sensible for modest N).
EXP_LAYOUTS = ("blocked", "full")

# Per-output-tile VMEM budget for choosing blocks (x + w + out + banks).
# Half of a ~16 MB core, leaving headroom for pipelining's double buffers.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One launch geometry for ``apsq_matmul``-family kernels.

    ``block_m == 1`` selects the m=1 fast-path kernel (whole-K, single
    grid row); any other value runs the generic (or expert) grid.
    ``source`` records where the config came from ("heuristic", "tuned",
    "override") so benchmark records can tell tuned runs from defaults.
    """

    block_m: int
    block_n: int
    exp_layout: str = "blocked"
    source: str = "heuristic"

    def as_record(self) -> dict:
        """The benchmark-record view (kernel_bench / serving_bench)."""
        return {"block_m": self.block_m, "block_n": self.block_n,
                "exp_layout": self.exp_layout, "blocks_source": self.source}


def shape_class(m: int, *, expert: bool = False, attn: bool = False) -> str:
    """Bucket a GEMM by its M extent (the serving-relevant axis)."""
    if attn:
        return "prefill_attn"
    if expert:
        return "expert"
    if m == 1:
        return "decode_m1"
    if m <= 32:
        return "small_m"
    return "prefill"


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _fit_block(dim: int, cap: int, mult: int) -> int:
    """Largest useful block for ``dim``: the whole (padded) dim if it is
    below ``cap``, else ``cap``.  Always a multiple of ``mult``."""
    return min(cap, _round_up(max(dim, 1), mult))


def _vmem_bytes(bm: int, bn: int, bk: int, gs: int, n_p: int,
                exp_layout: str, n: int) -> int:
    """Working set of one output tile: x/w blocks, INT32 out, INT8 banks,
    and the exponent block (INT32)."""
    exps = n_p * (n if exp_layout == "full" else bn) * 4
    return bm * bk + bk * bn + 4 * bm * bn + gs * bm * bn + exps


def _clamp_to_budget(bm: int, bn: int, k: int, n_p: int, gs: int,
                     exp_layout: str, n: int) -> tuple[int, int, str]:
    """Shrink (bn first, then bm) until the tile fits the VMEM budget."""
    bk = _round_up(k, n_p) // n_p
    while (_vmem_bytes(bm, bn, bk, gs, n_p, exp_layout, n)
           > VMEM_BUDGET_BYTES):
        if exp_layout == "full":
            exp_layout = "blocked"
        elif bn > 128:
            bn = max(128, bn // 2)
        elif bm > 8:
            bm = max(8, bm // 2)
        else:
            break
    return bm, bn, exp_layout


def heuristic_config(cls: str, m: int, k: int, n: int, *, n_p: int,
                     gs: int) -> BlockConfig:
    """Static per-shape-class fallback — never measures anything.

    decode_m1 runs the fast-path kernel (block_m=1, whole K inlined);
    the other classes take the largest tiles that cover the padded dims
    under the VMEM budget, so small shapes get single-launch grids and
    large ones get MXU-aligned 8/128 multiples.
    """
    if cls == "decode_m1":
        bm, bn = 1, _fit_block(n, 512, 128)
    elif cls == "small_m":
        bm, bn = _fit_block(m, 32, 8), _fit_block(n, 512, 128)
    elif cls == "expert":
        bm, bn = _fit_block(m, 128, 8), _fit_block(n, 256, 128)
    elif cls == "prefill_attn":
        # m = chunk rows (all resident in the q tile), n = KV sequence;
        # block_n is the flash-decode block_s KV tile.
        bm, bn = _fit_block(m, 32, 8), _fit_block(n, 512, 128)
    else:  # prefill
        bm, bn = _fit_block(m, 256, 8), _fit_block(n, 512, 128)
    bm, bn, layout = _clamp_to_budget(bm, bn, k, n_p, gs, "blocked", n)
    return BlockConfig(bm, bn, layout, source="heuristic")


def candidate_configs(cls: str, m: int, k: int, n: int, *, n_p: int,
                      gs: int) -> list[BlockConfig]:
    """The deterministic, VMEM-feasible candidate set for one class.

    decode_m1 pins block_m=1 (the fast path has no other M geometry) and
    the expert class pins the "blocked" exponent layout (the fused expert
    kernel keeps per-expert exponent banks blocked per column tile).
    """
    if cls == "decode_m1":
        bms = [1]
    elif cls == "prefill_attn":
        # The chunk's query rows all sit in one q tile; only the KV tile
        # (block_n -> block_s) is searchable geometry.
        bms = [_fit_block(m, 32, 8)]
    else:
        caps = (8, 32, 64, 128, 256)
        bms = sorted({_fit_block(m, c, 8) for c in caps})
    bns = sorted({_fit_block(n, c, 128) for c in (128, 256, 512)})
    layouts = ("blocked",) if cls in ("expert", "decode_m1", "prefill_attn") \
        else EXP_LAYOUTS
    out = []
    for bm in bms:
        for bn in bns:
            for layout in layouts:
                bk = _round_up(k, n_p) // n_p
                if (_vmem_bytes(bm, bn, bk, gs, n_p, layout, n)
                        <= VMEM_BUDGET_BYTES):
                    out.append(BlockConfig(bm, bn, layout,
                                           source="tuned"))
    return out


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def cache_path() -> str | None:
    """The tuned table's file: ``$REPRO_AUTOTUNE_CACHE``, else None."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def cache_key(cls: str, n_p: int, gs: int, backend: str | None = None) -> str:
    backend = backend or jax.default_backend()
    return f"{cls}|np={n_p}|gs={gs}|{backend}"


_CACHE_MEM: dict[str, dict] = {}


def _load_cache(path: str | None = None, *, refresh: bool = False) -> dict:
    path = path or cache_path()
    if path is None:
        return {}
    if refresh or path not in _CACHE_MEM:
        try:
            with open(path) as f:
                payload = json.load(f)
            entries = payload.get("entries", {}) \
                if payload.get("version") == CACHE_VERSION else {}
        except (OSError, ValueError):
            entries = {}
        _CACHE_MEM[path] = entries
    return _CACHE_MEM[path]


def _store_cache(entries: dict, path: str | None = None) -> None:
    path = path or cache_path()
    if path is None:
        raise ValueError("no autotune table: pass path= or set "
                         "REPRO_AUTOTUNE_CACHE")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)
    _CACHE_MEM[path] = entries


def clear_memory_cache() -> None:
    """Drop the in-process view of the on-disk table (tests: cold reload)."""
    _CACHE_MEM.clear()


def get_block_config(m: int, k: int, n: int, *, n_p: int, gs: int,
                     expert: bool = False, attn: bool = False,
                     path: str | None = None) -> BlockConfig:
    """The launch-time lookup: cached winner if tuned, else heuristic.

    Pure and timing-free — safe to call at trace time (ops.py calls it
    whenever ``block_m``/``block_n`` are left as None).  The cached entry
    is clamped to the actual padded dims so a winner tuned at a large
    representative shape stays legal on a smaller same-class shape.
    """
    cls = shape_class(m, expert=expert, attn=attn)
    entry = _load_cache(path).get(cache_key(cls, n_p, gs))
    if entry is not None:
        bm = min(int(entry["block_m"]), _round_up(m, 8)) \
            if entry["block_m"] > 1 else 1
        bn = min(int(entry["block_n"]), _round_up(n, 128))
        return BlockConfig(bm, bn, str(entry.get("exp_layout", "blocked")),
                           source="tuned")
    return heuristic_config(cls, m, k, n, n_p=n_p, gs=gs)


# ---------------------------------------------------------------------------
# Measured tuning (explicit, eager — never runs from a trace)
# ---------------------------------------------------------------------------

def _default_measure(cfg: BlockConfig, m: int, k: int, n: int, *, n_p: int,
                     gs: int, expert: bool, reps: int,
                     interpret: bool) -> float:
    """Wall-clock one config (jit + warmup + ``block_until_ready``), us."""
    import jax.numpy as jnp

    from .apsq_matmul import (apsq_expert_matmul_int8, apsq_matmul_int8,
                              choose_exps)

    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (m, k), -128, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128,
                           jnp.int8)
    base = choose_exps(x, w, n_p=n_p, gs=gs)
    # Per-column exponents so the exp_layout axis is actually exercised.
    exps = base[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :] % 2
    if expert:
        E = 4
        xe = jnp.broadcast_to(x, (E, m, k))
        we = jnp.broadcast_to(w, (E, k, n))
        ee = jnp.broadcast_to(exps, (E,) + exps.shape)
        f = lambda: apsq_expert_matmul_int8(
            xe, we, ee, gs=gs, block_m=cfg.block_m, block_n=cfg.block_n,
            interpret=interpret)
    else:
        f = lambda: apsq_matmul_int8(
            x, w, exps, gs=gs, block_m=cfg.block_m, block_n=cfg.block_n,
            exp_layout=cfg.exp_layout, interpret=interpret)
    jax.block_until_ready(f())  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _default_measure_attn(cfg: BlockConfig, m: int, k: int, n: int, *,
                          n_p: int, gs: int, expert: bool, reps: int,
                          interpret: bool) -> float:
    """Wall-clock one chunked KV-attention launch: m = chunk query rows,
    k = head dim, n = KV sequence; ``cfg.block_n`` is the requested
    ``block_s`` KV tile (snapped to a divisor of S, as at serving time)."""
    import jax.numpy as jnp

    from .int8_kv_attention import int8_kv_attention

    B, Hkv, G = 1, 4, 2
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, m, Hkv * G, k), jnp.float32)
    kc = jax.random.randint(jax.random.fold_in(key, 1), (B, n, Hkv, k),
                            -128, 128, jnp.int8)
    vc = jax.random.randint(jax.random.fold_in(key, 2), (B, n, Hkv, k),
                            -128, 128, jnp.int8)
    exps = jnp.full((B, Hkv), -7, jnp.int32)
    block_s = max(1, min(cfg.block_n, n))
    while n % block_s:
        block_s -= 1
    f = lambda: int8_kv_attention(q, kc, vc, exps, exps, n,
                                  block_s=block_s, interpret=interpret)
    jax.block_until_ready(f())  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def tune(m: int, k: int, n: int, *, n_p: int, gs: int,
         expert: bool = False, attn: bool = False, reps: int = 3,
         path: str | None = None,
         interpret: bool = False, measure=None,
         verbose=None) -> BlockConfig:
    """Measure every candidate for this shape's class and cache the winner.

    ``measure(cfg, m, k, n, n_p=..., gs=..., expert=..., reps=...,
    interpret=...) -> us`` is injectable (tests use a deterministic fake).
    Ties and near-ties resolve to the earliest candidate in the sorted,
    deterministic candidate order, so the same measurements always yield
    the same winner.
    """
    cls = shape_class(m, expert=expert, attn=attn)
    measure = measure or (_default_measure_attn if attn
                          else _default_measure)
    best_cfg, best_us = None, float("inf")
    for cfg in candidate_configs(cls, m, k, n, n_p=n_p, gs=gs):
        us = measure(cfg, m, k, n, n_p=n_p, gs=gs, expert=expert,
                     reps=reps, interpret=interpret)
        if verbose:
            verbose(f"autotune,{cls},bm={cfg.block_m},bn={cfg.block_n},"
                    f"{cfg.exp_layout},{us:.0f}us")
        if us < best_us:
            best_cfg, best_us = cfg, us
    assert best_cfg is not None, "no feasible candidate config"
    entries = dict(_load_cache(path))
    entries[cache_key(cls, n_p, gs)] = {
        "block_m": best_cfg.block_m, "block_n": best_cfg.block_n,
        "exp_layout": best_cfg.exp_layout, "us": round(best_us, 1),
        "m": m, "k": k, "n": n,
    }
    _store_cache(entries, path)
    return best_cfg


# Representative shapes per class for whole-table tuning: the serving
# shapes kernel_bench tracks (decode/prefill at tinyllama-ish dims) and a
# capacity-sized expert GEMM.
STANDARD_SHAPES = (
    ("decode_m1", dict(m=1, k=1024, n=512, expert=False)),
    ("small_m", dict(m=16, k=1024, n=512, expert=False)),
    ("prefill", dict(m=256, k=1024, n=512, expert=False)),
    ("expert", dict(m=64, k=512, n=256, expert=True)),
    # Chunked-prefill KV attention: m = chunk rows, k = head dim,
    # n = gathered KV sequence.
    ("prefill_attn", dict(m=16, k=64, n=512, expert=False, attn=True)),
)


def tune_standard_shapes(*, n_p: int = 8, gs: int = 2, reps: int = 3,
                         path: str | None = None,
                         interpret: bool = False, measure=None,
                         verbose=None) -> dict[str, BlockConfig]:
    """Tune every shape class at its representative shape; returns winners."""
    out = {}
    for cls, shp in STANDARD_SHAPES:
        out[cls] = tune(shp["m"], shp["k"], shp["n"], n_p=n_p, gs=gs,
                        expert=shp["expert"], attn=shp.get("attn", False),
                        reps=reps, path=path,
                        interpret=interpret, measure=measure,
                        verbose=verbose)
    return out


def resolved_table(*, n_p: int = 8, gs: int = 2,
                   shapes=STANDARD_SHAPES) -> dict[str, dict]:
    """What ``get_block_config`` currently resolves per shape class —
    benchmark records embed this so tuned vs default runs are
    distinguishable in the checked-in BENCH JSONs."""
    out = {}
    for cls, shp in shapes:
        cfg = get_block_config(shp["m"], shp["k"], shp["n"], n_p=n_p,
                               gs=gs, expert=shp["expert"],
                               attn=shp.get("attn", False))
        out[cls] = cfg.as_record()
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--show", action="store_true",
                    help="print the resolved table without tuning")
    ap.add_argument("--n-p", type=int, default=8)
    ap.add_argument("--gs", type=int, default=2)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cache", default=None,
                    help="table file to write (default: "
                         "$REPRO_AUTOTUNE_CACHE; one of them is needed)")
    args = ap.parse_args(argv)
    if args.show:
        for cls, rec in resolved_table(n_p=args.n_p, gs=args.gs).items():
            print(f"{cls:10s} {rec}")
        return 0
    winners = tune_standard_shapes(n_p=args.n_p, gs=args.gs,
                                   reps=args.reps, path=args.cache,
                                   verbose=print)
    for cls, cfg in winners.items():
        print(f"{cls:10s} -> block_m={cfg.block_m} block_n={cfg.block_n} "
              f"exp_layout={cfg.exp_layout}")
    print(f"cached -> {args.cache or cache_path()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
