"""Quickstart: APSQ in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

1. Quantize one GEMM's partial sums to INT8 with Algorithm 1 (APSQ +
   grouping) and measure the error vs the fp32 result.
2. Run the true-integer Pallas kernel (interpret mode on CPU) and verify
   it agrees bit-exactly with the integer oracle.
3. Ask the paper's analytical accelerator model what that buys in energy.
4. Per-layer policy on a whole model: attention GEMMs at gs=2/n_p=4,
   FFN GEMMs at gs=4/n_p=8 (the RAE reconfigures per layer), capture-based
   calibration, integer export, and deployed serving.
5. Backend selection: the calibrate -> export -> kernel-serving flow.
   Deployed GEMMs dispatch through the ``repro.exec`` registry —
   ``oracle`` (jnp reference), ``pallas`` (the real kernel;
   ``PallasBackend(interpret=True)`` on CPU), ``auto`` (kernel on TPU,
   oracle elsewhere) — and greedy
   decodes are token-for-token identical across backends.
6. Search a policy: ``repro.search`` walks an architecture's actual GEMM
   inventory (one layer namespace shared with the quantizer), scores
   per-layer (gs, n_p) policies on energy x accuracy, and returns the
   Pareto front.  Full loop:
   ``python -m repro.search.cli --arch tinyllama-1.1b --budget-smoke``.
8. Serve across a mesh: shard the exported code banks + KV pools over a
   "model" axis and decode with INT8-on-the-wire collectives, bit-exact
   vs single-device.  Needs >= 2 devices — rerun with
   ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` to see it.

Block autotuning: every Pallas launch resolves its (block_m, block_n,
exponent layout) per shape class through ``repro.kernels.autotune`` —
decode (M=1) takes a single-row fast-path kernel, prefill gets large
MXU-aligned tiles, MoE expert banks run one fused grid over all experts.
The default is a static heuristic (nothing is ever timed at trace time);
``PYTHONPATH=src python -m repro.kernels.autotune --cache t.json``
measures the real candidates on this host and writes the winners to
``t.json``; with ``REPRO_AUTOTUNE_CACHE=t.json`` set, every kernel
launch — including the serving engines below — picks them up.
``python -m repro.kernels.autotune --show`` prints the resolved table.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (QuantConfig, calibrate_dense, quant_dense,
                        quant_params_init)
from repro.energy import AcceleratorConfig, LayerShape, layer_energy
from repro.kernels.apsq_matmul import (apsq_matmul_int8, apsq_matmul_ref,
                                       choose_exps)

key = jax.random.PRNGKey(0)

# --- 1. fake-quant QAT path ------------------------------------------------
x = jax.random.normal(key, (64, 512))                  # activations
w = jax.random.normal(jax.random.fold_in(key, 1), (512, 256)) * 0.05
ref = x @ w

for mode, cfg in [
    ("w8a8 (no psum quant)", QuantConfig.w8a8()),
    ("psq  (independent tiles)", QuantConfig.psq(n_p=8)),
    ("apsq gs=1", QuantConfig.apsq(gs=1, n_p=8)),
    ("apsq gs=2", QuantConfig.apsq(gs=2, n_p=8)),
    ("apsq gs=4", QuantConfig.apsq(gs=4, n_p=8)),
]:
    qp = calibrate_dense(quant_params_init(w, cfg), x, w, cfg)
    y = quant_dense(x, w, qp, cfg)
    rel = float(jnp.mean(jnp.abs(y - ref)) / jnp.mean(jnp.abs(ref)))
    print(f"{mode:28s} rel-err {rel:.4f}")

# --- 2. true-integer deployment kernel --------------------------------------
xq = jax.random.randint(key, (64, 512), -128, 128, jnp.int8)
wq = jax.random.randint(jax.random.fold_in(key, 2), (512, 256), -128, 128,
                        jnp.int8)
exps = choose_exps(xq, wq, n_p=8, gs=2)
kern = apsq_matmul_int8(xq, wq, exps, gs=2, interpret=True)
oracle = apsq_matmul_ref(xq, wq, exps, n_p=8, gs=2)
print(f"\nPallas kernel bit-exact vs oracle: "
      f"{bool(jnp.all(kern == oracle))}")

# --- 3. what it buys (paper eqs 1-6) ----------------------------------------
layer = LayerShape("ffn", tokens=128, c_i=768, c_o=3072)
acc = AcceleratorConfig()
e32 = layer_energy(layer, acc, "WS", psum_bits=32)
e8 = layer_energy(layer, acc, "WS", psum_bits=8, gs=2)
print(f"\nBERT FFN layer, WS dataflow: INT32-PSUM {e32['total']:.2e} J "
      f"-> APSQ INT8 {e8['total']:.2e} J "
      f"({100 * (1 - e8['total'] / e32['total']):.0f}% saved)")

# --- 4. per-layer policy -> calibrate -> export -> integer serving ----------
from repro.models.config import ModelConfig
from repro.models.model import forward, init_lm
from repro.quant import QuantPolicy, calibrate_model, export_quantized
from repro.exec import PallasBackend
from repro.serving import Request, ServingEngine

policy = QuantPolicy.of(
    ("*.mix.*", QuantConfig.apsq(gs=2, n_p=4)),   # attention projections
    ("*.ffn.*", QuantConfig.apsq(gs=4, n_p=8)),   # FFN projections
    default=QuantConfig.w8a8(),                   # everything else W8A8
)
cfg = ModelConfig(name="quickstart", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  dtype="float32", scan_layers=False).with_quant(policy)
params = init_lm(jax.random.PRNGKey(3), cfg)
wq_spec = params["units"]["u0"]["0"]["mix"]["wq"]["qp"].spec
wi_spec = params["units"]["u0"]["0"]["ffn"]["wi"]["qp"].spec
print(f"\nper-layer policy: mix.wq -> gs={wq_spec.psum.gs} "
      f"n_p={wq_spec.psum.n_p}; ffn.wi -> gs={wi_spec.psum.gs} "
      f"n_p={wi_spec.psum.n_p}")

tok = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
params = calibrate_model(params, cfg, {"tokens": tok})   # capture-based
logits = forward(params, cfg, tok)
print(f"calibrated QAT forward: {logits.shape}, "
      f"finite={bool(jnp.all(jnp.isfinite(logits)))}")

deploy, report = export_quantized(params)
int8_total = sum(r["int8_bytes"] * r["count"] for r in report.values())
print(f"export: {len(report)} layer groups, {int8_total / 1024:.0f} KiB of "
      f"INT8 weight codes")
engine = ServingEngine(deploy, cfg, max_batch=2, cache_len=64,
                       prefill_chunk=8)
done = engine.run([Request(uid=0, tokens=np.arange(6) % cfg.vocab,
                           max_new_tokens=8)])
print(f"integer-deployed engine decoded: {done[0].out}")

# --- 5. backend selection: serve the calibrated model through the kernel ----
# ``from_exported`` exports and serves in one call; ``backend=`` picks the
# executor.  "auto" (default) runs the Pallas kernel on TPU and the
# bit-identical jnp oracle elsewhere; PallasBackend(interpret=True) runs
# the kernel in the Pallas interpreter on CPU — same integers,
# token-for-token.
prompt = np.arange(6) % cfg.vocab
decodes = {}
for backend in ("oracle", PallasBackend(interpret=True)):
    eng = ServingEngine.from_exported(params, cfg, max_batch=1, cache_len=64,
                                      prefill_chunk=8, backend=backend)
    decodes[eng.backend.name] = eng.run([Request(uid=1, tokens=prompt,
                                        max_new_tokens=6)])[0].out
print(f"\nkernel-served decode ({'==' if decodes['oracle'] == decodes['pallas'] else '!='} oracle): "
      f"{decodes['pallas']}")
assert decodes["oracle"] == decodes["pallas"]

# --- 6. search a policy: energy x accuracy co-exploration --------------------
# ``repro.search.inventory`` names every GEMM of an architecture with the
# SAME stable names the quantizer uses, so one QuantPolicy drives both the
# analytical energy model (full-size shapes) and the fake-quant accuracy
# proxy.  Here: score three policies on TinyLlama's real GEMM walk; the
# CLI (see module docstring) runs the full candidate-generation + Pareto +
# calibrate->export->pallas round-trip loop.
from repro.configs import get_config
from repro.search import energy_report, model_inventory

cfg_full = get_config("tinyllama-1.1b")
inv = model_inventory(cfg_full, seq_len=4096)
print(f"\npolicy search: {len(inv)} named GEMMs on {cfg_full.name} "
      f"(e.g. {inv[0].shape.name})")
for pname, pol in [
    ("uniform w8a8", QuantPolicy.uniform(QuantConfig.w8a8())),
    ("uniform apsq(gs=2)", QuantPolicy.uniform(QuantConfig.apsq(gs=2))),
    ("ffn-only apsq", QuantPolicy.of(("*.ffn.*", QuantConfig.apsq(gs=2)),
                                     default=QuantConfig.w8a8())),
]:
    r = energy_report(cfg_full, pol, inventory=inv)
    print(f"  {pname:20s} E={r['energy_j']:.2e} J "
          f"(saves {r['saving']:.0%} vs INT32 PSUM)")

# --- 7. serve many streams: continuous batching over INT8 KV pages ----------
# The production serving path: calibrate -> export -> PagedServingEngine.
# Every attention layer's cache is a pool of fixed-size INT8 pages with
# power-of-two scales (the paper's shift-only dequant argument applied to
# the KV cache); a host-side scheduler admits requests as slots and pages
# free up, grows each stream's page list on demand, and — when the pool
# runs dry — preempts the latest-admitted stream and resumes it later
# with bit-identical output.  Prompts prefill CHUNKED: up to
# ``prefill_chunk`` tokens per forward (every GEMM at m=chunk, attention
# with an in-chunk causal mask against the paged cache), writing the same
# INT8 codes and exponents the old token-by-token scan wrote — bit
# identical, just ~chunk-times fewer dispatches, so TTFT drops.  Each
# engine step spends a ``prefill_token_budget`` on pending prompts before
# decoding all in-flight slots, so long prompts interleave with decodes
# instead of stalling them; raise ``prefill_chunk`` (and the budget) for
# prompt-heavy loads.  Decode attention reads go through the second
# ``repro.exec`` op family (``kv_attention``: Pallas flash-decode kernel
# on TPU, jnp oracle elsewhere — the chunk rides its query-row axis, the
# "prefill_attn" autotune class), so weights AND cache are integer end to
# end.  Decode itself runs FUSED: every heartbeat scans up to
# ``decode_horizon`` (pow2, default 8) decode steps inside one jitted
# ``lax.scan`` — sampling, per-stream EOS/max-token stops, position
# advance and KV writes all on device, one host sync per macro-step
# draining a [batch, horizon] token block.  The scheduler pre-reserves
# each stream's pages over the horizon and shrinks a stream's budget
# (never preempting) when the pool is tight.  Raise the horizon when
# decode is dispatch-bound (host round-trips per token dominate — the
# usual case once kernels are fast); keep it at 1 for very tight page
# pools or a strict per-token latency SLO, since tokens surface to the
# host a macro-step at a time.  H fused steps stay token- AND
# KV-bit-identical to H single steps, so the parity story is unchanged.
# ``benchmarks/serving_bench.py`` drives this engine with hundreds of
# Poisson-arrival streams and reports tokens/s, prefill tokens/s,
# p50/p99, a host-overhead breakdown, and a --decode-horizon sweep;
# ``benchmarks/check_serving_floor.py`` holds CI to the committed
# floors plus the fused-vs-per-token speedup.
from repro.serving import PagedServingEngine

paged = PagedServingEngine.from_exported(
    params, cfg, max_batch=4, page_size=8, n_pages=33, prefill_chunk=8,
    decode_horizon=4)
streams = [Request(uid=i, tokens=(np.arange(5 + i) * 3) % cfg.vocab,
                   max_new_tokens=6) for i in range(8)]
done = paged.run(streams)
solo = PagedServingEngine.from_exported(
    params, cfg, max_batch=1, page_size=8, n_pages=33, prefill_chunk=8,
    decode_horizon=1)                      # per-token heartbeat reference
ref = solo.run([Request(uid=0, tokens=(np.arange(5) * 3) % cfg.vocab,
                        max_new_tokens=6)])[0].out
batched0 = next(r.out for r in done if r.uid == 0)
print(f"\npaged INT8 serving: {len(done)} streams on 4 slots "
      f"({paged.sched.stats.admitted} admissions, "
      f"{paged.sched.stats.preempted} preemptions, "
      f"{paged.decode_dispatches} fused decode launches), "
      f"batched h4 == single-stream h1: {batched0 == ref}")
assert batched0 == ref

# --- 8. serve across a mesh: tensor/expert-parallel integer serving ----------
# ``mesh=`` shards the SAME exported tree over the "model" axis —
# ``repro.dist.tp`` places each code bank by its Algorithm-1 mode (K by
# whole PSUM tiles for PSQ/W8A8 so int32 partials combine exactly; N for
# APSQ, whose group-start chain is sequential along K; the expert axis
# for MoE banks) and the KV pools over kv-heads.  Collectives move INT8
# codes, not fp32 partials (``wire="fp32"`` is the parity-debug path —
# same tokens, ~4x the bytes; ``engine.shard_plan`` + ``wire_report``
# price every collective analytically, see benchmarks/dist_bench.py).
# Recipe: calibrate -> from_exported(mesh=...) -> decode -> compare
# against the single-device engine.  Same integers, token-for-token.
if len(jax.devices()) >= 2:
    from repro.dist import wire_report
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((1, 2))               # ("data", "model")
    sharded = PagedServingEngine.from_exported(
        params, cfg, max_batch=1, page_size=8, n_pages=33,
        prefill_chunk=8, mesh=mesh, wire="int8")
    out = sharded.run([Request(uid=0, tokens=(np.arange(5) * 3) % cfg.vocab,
                               max_new_tokens=6)])[0].out
    wr = wire_report(sharded.shard_plan, m=1)
    print(f"mesh-served decode == single-device: {out == ref}; "
          f"switchable collectives int8/fp32 = "
          f"{wr['switchable']['ratio'] or 1.0:.1f}x fewer bytes")
    assert out == ref
else:
    print("\nmesh serving: skipped (1 device; set XLA_FLAGS="
          "--xla_force_host_platform_device_count=2 to run step 8)")
