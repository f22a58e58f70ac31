"""Chip smoke test: serve tinyllama-1.1b at published widths on a TPU.

Drives the serving main path once, in one process, through the entry
points a user calls (``repro.launch.serve.build_model`` /
``build_engine``): random weights from ``--seed`` -> ``calibrate_model``
-> ``PagedServingEngine.from_exported`` -> INT8 APSQ GEMMs and paged INT8
KV attention through the compiled Pallas kernels.  It requires the served
decode step to contain the kernels (``tpu_custom_call``), so a silent
fallback to jnp cannot pass, and then checks the results against the jnp
oracle on the same chip:

* integer GEMMs: the same requests served with the oracle's GEMMs (and
  the same attention kernel) must give identical greedy tokens — the
  bit-exact integer contract;
* attention, which is float: the kernel must match the oracle's
  ``int8_kv_attention_ref`` within a stated tolerance at the serving
  shapes;
* the whole oracle backend serves the requests too; its tokens are
  reported, not required equal: its attention rounds differently in the
  last float bits, and a model with random weights has near-tied bf16
  logits, so greedy tokens can part.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the same model on a
                                       # 1x4 mesh against one chip

Exits non-zero, before any other work, when JAX finds no TPU.  The last
line of a passing run is one JSON object naming the device.  Every time
printed is wall-clock from this one run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parent
ARCH = "tinyllama-1.1b"
PAGE_SIZE = 16                 # = the engine's prefill chunk
PROMPT_LENS = (12, 16, 24, 40)  # two prompts span more than one chunk
NEW_TOKENS = 16
MAX_BATCH = 4
CACHE_LEN = 128                # tokens per slot: prompt + output fit
# Kernel vs oracle attention in f32.  The outputs are convex combinations
# of dequantized values of magnitude ~1; the kernel's blockwise online
# softmax and the chip's f32 dot and exp differ from the oracle's by
# about an f32 ulp per summed term, ~1e-6 over CACHE_LEN keys.  A wrong
# mask, scale or head mapping is off by orders of magnitude more.
ATTN_RTOL, ATTN_ATOL = 1e-5, 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def snapshot(self):
        return (self.seconds, self.events["cache_hits"],
                self.events["cache_misses"])


def phase(name: str, stats: CompileStats, fn):
    """Run one phase; print its wall time, compile time and cache hits."""
    s0, h0, m0 = stats.snapshot()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    s1, h1, m1 = stats.snapshot()
    log(f"phase {name}: ok, wall-clock {wall:.1f}s, of which backend "
        f"compile {s1 - s0:.1f}s; compile cache hits {h1 - h0}, misses "
        f"{m1 - m0} (not a benchmark)")
    return out


def make_requests(vocab: int, seed: int):
    import numpy as np

    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, vocab, size=n),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]


def serve(params, cfg, backend, seed: int, mesh=None) -> tuple:
    """Serve the smoke requests; returns (engine, {uid: tokens})."""
    from repro.launch.serve import build_engine
    eng = build_engine(params, cfg, engine="paged", exported=True,
                       backend=backend, mesh=mesh, max_batch=MAX_BATCH,
                       cache_len=CACHE_LEN, page_size=PAGE_SIZE)
    done = eng.run(make_requests(cfg.vocab, seed))
    out = {r.uid: list(r.out) for r in done}
    if sorted(out) != list(range(len(PROMPT_LENS))) or any(
            len(t) != NEW_TOKENS for t in out.values()):
        raise SystemExit(f"served {({u: len(t) for u, t in out.items()})} "
                         f"tokens per request, want {NEW_TOKENS} each")
    log(f"{eng.backend.name}: {len(out)} requests, "
        f"{sum(map(len, out.values()))} tokens")
    return eng, out


def oracle_gemms(attention):
    """A backend with the oracle's integer GEMMs and ``attention``'s KV
    attention: served tokens then depend on the GEMM path alone."""
    from repro.exec import OracleBackend

    class OracleGemms(OracleBackend):
        name = "oracle GEMMs + kernel attention"

        def kv_attention(self, *a, **kw):
            return attention.kv_attention(*a, **kw)

    return OracleGemms()


def kernel_calls(eng) -> int:
    """``tpu_custom_call`` sites in the engine's lowered decode step."""
    import jax.numpy as jnp
    b = eng.max_batch
    zeros = jnp.zeros(b, jnp.int32)
    text = eng._decode.lower(
        eng.decode_horizon, eng.params, eng.state,
        jnp.zeros((b, 1), jnp.int32), zeros, jnp.asarray(eng.sched.table),
        jnp.ones(b, bool), zeros + 1, zeros + 1, zeros - 1,
        eng.rng).as_text()
    return text.count("tpu_custom_call")


def first_divergence(a: dict, b: dict) -> dict:
    """{uid: index of the first differing token} for requests that part."""
    return {u: next(i for i, (x, y) in enumerate(zip(a[u], b[u])) if x != y)
            for u in sorted(a) if a[u] != b[u]}


def require_same(a: dict, b: dict, what: str) -> None:
    parted = first_divergence(a, b)
    if parted:
        raise SystemExit(f"{what}: tokens differ, first at {parted}: "
                         f"{a} vs {b}")
    log(f"{what}: tokens identical ({len(a)} requests)")


def check_attention(cfg, seed: int) -> None:
    """The attention kernel against its jnp oracle at the serving shapes:
    a decode batch and a prefill chunk over the slot's gathered pages."""
    import jax.numpy as jnp
    import numpy as np

    from repro.exec import PallasBackend, execute_kv_attention
    from repro.kernels.int8_kv_attention import quantize_kv_po2
    key = jax.random.PRNGKey(seed)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    for batch, rows in ((MAX_BATCH, 0), (1, PAGE_SIZE)):
        ks = jax.random.split(jax.random.fold_in(key, rows), 3)
        q = jax.random.normal(ks[0], (batch, hq, hd) if rows == 0
                              else (batch, rows, hq, hd), jnp.float32)
        kc, ke = quantize_kv_po2(jax.random.normal(
            ks[1], (batch, CACHE_LEN, hkv, hd)))
        vc, ve = quantize_kv_po2(jax.random.normal(
            ks[2], (batch, CACHE_LEN, hkv, hd)))
        length = jnp.asarray([CACHE_LEN - 7 * i for i in range(batch)],
                             jnp.int32)
        got, want = (np.asarray(execute_kv_attention(
            q, kc, vc, ke, ve, length, backend=be))
            for be in (PallasBackend(interpret=False), "oracle"))
        np.testing.assert_allclose(got, want, rtol=ATTN_RTOL,
                                   atol=ATTN_ATOL)
        log(f"attention {'decode' if rows == 0 else 'prefill chunk'} "
            f"{q.shape}: kernel vs oracle max |diff| "
            f"{float(np.max(np.abs(got - want))):.3g}, within rtol "
            f"{ATTN_RTOL} atol {ATTN_ATOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 1x4 mesh (one KV head per chip) and "
                         "compare with one chip; needs four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    log(f"device {dev.device_kind!r}, {n_dev} device(s)")
    want = 4 if args.four_chips else 1
    if n_dev < want:
        print(f"chip_smoke: {want} chips needed, found {n_dev}",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.exec import PallasBackend
    from repro.launch.serve import build_model, enable_compile_cache

    stats = CompileStats()
    log(f"compile cache: {enable_compile_cache()}")
    cfg, params = phase("build (init + calibrate)", stats, lambda: build_model(
        ARCH, full=True, exported=True, seed=args.seed))
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, psum {cfg.quant.psum.mode} gs={cfg.quant.psum.gs} "
        f"n_p={cfg.quant.psum.n_p}")
    pallas = PallasBackend(interpret=False)
    eng, tokens = phase("serve pallas, one chip", stats,
                        lambda: serve(params, cfg, pallas, args.seed))
    if args.four_chips:
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((1, 4))
        sharded = phase("serve pallas, 1x4 mesh, int8 wire", stats,
                        lambda: serve(params, cfg, pallas, args.seed,
                                      mesh=mesh)[1])
        require_same(tokens, sharded, "1x4 mesh vs one chip")
    else:
        leaf = eng.backend.resolve()
        calls = kernel_calls(eng)
        log(f"decode step: backend {leaf.name} (interpret="
            f"{leaf.interpret}), {calls} tpu_custom_call sites")
        if leaf.name != "pallas" or leaf.interpret or calls == 0:
            raise SystemExit("the served decode step runs no Pallas kernel")
        del eng
        _, ref = phase("serve oracle GEMMs", stats, lambda: serve(
            params, cfg, oracle_gemms(pallas), args.seed))
        require_same(tokens, ref, "pallas vs oracle GEMMs")
        phase("attention kernel vs oracle", stats,
              lambda: check_attention(cfg, args.seed))
        _, oracle = phase("serve oracle", stats, lambda: serve(
            params, cfg, "oracle", args.seed))
        parted = first_divergence(tokens, oracle)
        log(f"pallas vs whole oracle: {len(tokens) - len(parted)} of "
            f"{len(tokens)} requests identical; first differing token per "
            f"parted request {parted} (float attention, reported only)")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    log(f"peak_bytes_in_use on device 0: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
