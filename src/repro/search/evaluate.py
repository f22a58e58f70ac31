"""Policy evaluation: energy (analytical) x accuracy (fake-quant proxy).

Shared by ``repro.search.driver`` (the co-exploration loop),
``repro.search.cli``, and ``launch/dryrun.py`` (the ``--quant-policy``
sweep and ``--backend-parity`` cell reports import ``describe_policy`` /
``backend_parity_report`` from here) — one implementation of "what does
this policy cost and how wrong is it" for every surface.

Two axes, both cheap enough to run per candidate:

  * ``energy_report``  — the paper's analytical accelerator model (eqs
    1-6) over the architecture's *full-size* GEMM inventory, with each
    layer's (gs, psum_bits, n_p) resolved from the policy
    (``inventory.energy_specs``) — heterogeneous per-layer energy, scored
    against the INT32-PSUM baseline.
  * ``accuracy_proxy`` — fake-quant forward error vs the fp32 oracle on a
    calibration batch, at the arch's *smoke-scale* sibling (same family,
    CPU-sized).  Calibration is the capture-based ``calibrate_model``
    (the same taps QAT uses), so PSUM scales are data-driven, not
    generic — exactly the error the deployed integer path inherits.

``roundtrip_report`` proves a searched policy is *servable*: calibrate ->
``export_quantized`` -> execute through the Pallas kernel vs the jnp
oracle (GEMM-level bit parity on an exported layer + greedy decode parity
through ``ServingEngine``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.energy import AcceleratorConfig, model_energy
from repro.models.config import ModelConfig

from .inventory import energy_specs, model_inventory


# ---------------------------------------------------------------------------
# Policy description + backend parity (used by launch/dryrun.py cell reports)
# ---------------------------------------------------------------------------

def describe_policy(quant) -> list:
    """Human-readable rule list for a QuantPolicy (JSON-report friendly)."""
    def one(cfg):
        if cfg is None:
            return "float"
        if not cfg.enabled:
            return "disabled"
        if cfg.psum.mode == "none":
            return f"w{cfg.w_bits}a{cfg.a_bits}"
        return (f"{cfg.psum.mode}(gs={cfg.psum.gs},n_p={cfg.psum.n_p},"
                f"bits={cfg.psum.bits})")

    rules = [[r.pattern, one(r.config)]
             for r in getattr(quant, "rules", ())]
    rules.append(["<default>", one(getattr(quant, "default", quant))])
    return rules


def policy_sweep(arg: str) -> list:
    """Resolve a ``--quant-policy`` argument to ``[(label, policy)]``.

    ``arg`` is a preset name from ``repro.quant.policy_presets`` or
    ``'all'`` for the whole registry — the sweep resolution shared by
    ``launch/dryrun.py`` and the search CLI.
    """
    from repro.quant import policy_presets

    presets = policy_presets()
    names = sorted(presets) if arg == "all" else [arg]
    try:
        return [(f"policy_{n}", presets[n]) for n in names]
    except KeyError:
        raise KeyError(f"unknown --quant-policy {arg!r}; "
                       f"known: {sorted(presets)} or 'all'") from None


def backend_parity_report(cfg: ModelConfig, m: int = 8) -> dict:
    """Oracle-vs-pallas execution check at the arch's GEMM shape.

    Exports one calibrated [d_model, d_model] linear under the cfg's
    policy and runs it through ``repro.exec.backend_parity_check``
    (pallas in interpret mode) — the side-by-side parity +
    wall-clock the roofline table reports next to each quantized cell.
    """
    from repro.core import quant_params_init, calibrate_dense
    from repro.exec import backend_parity_check
    from repro.quant.export import export_quantized
    from repro.quant.policy import resolve_quant

    # Probe the policy at representative layer names and prefer a
    # PSUM-quantized resolution — a sweep like "ffn_only" must be
    # parity-checked on the APSQ path it exists to measure, not on
    # whatever plain-W8A8 config the first attention layer resolves to.
    probe, resolved = None, None
    for name in ("unit.0.mix.wq", "unit.0.ffn.wi", "rem.0.mix.wq",
                 "encoder.unit.0.mix.wq", "head"):
        r = resolve_quant(cfg.policy, name)
        if r is None:
            continue
        if resolved is None or (resolved.psum.mode == "none"
                                and r.psum.mode != "none"):
            probe, resolved = name, r
        if resolved.psum.mode != "none":
            break
    if resolved is None:
        return {"skipped": "no quantized layers under this policy"}
    k = min(cfg.d_model, 512)  # representative reduction dim, CPU-cheap
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, k)) * 0.05
    qp = calibrate_dense(quant_params_init(w, resolved, name=probe), x, w)
    dep, _ = export_quantized({"lin": {"w": w, "qp": qp}})
    _, times, bit_equal = backend_parity_check(dep["lin"]["qp"], x)
    return {"bit_equal": bit_equal, "layer": probe, "shape": [m, k, k],
            "mode": resolved.psum.mode, "gs": resolved.psum.gs,
            "n_p": resolved.psum.n_p,
            **{f"{name}_us": round(t, 1) for name, t in times.items()}}


# ---------------------------------------------------------------------------
# Energy axis
# ---------------------------------------------------------------------------

def energy_report(cfg: ModelConfig, policy, *, seq_len: int = 4096,
                  stage: str = "prefill", dataflow: str = "WS",
                  acc: AcceleratorConfig | None = None,
                  inventory: list | None = None) -> dict:
    """Heterogeneous per-layer energy of ``policy`` on ``cfg``'s GEMMs.

    Returns total/psum energy under the policy, the INT32-PSUM baseline,
    and the fractional saving — the energy coordinate of one search point.
    Pass ``inventory`` to reuse a precomputed walk across candidates.
    """
    if acc is None:
        acc = (AcceleratorConfig.llm_decode() if stage == "decode"
               else AcceleratorConfig())
    if inventory is None:
        inventory = model_inventory(cfg, seq_len, stage)
    shapes = [e.shape for e in inventory]
    base = model_energy(shapes, acc, dataflow, psum_bits=32)
    e = model_energy(energy_specs(inventory, policy, acc), acc, dataflow)
    return {
        "energy_j": e["total"], "psum_j": e["psum"],
        "baseline_j": base["total"],
        "saving": 1.0 - e["total"] / base["total"],
        "dataflow": dataflow, "seq_len": seq_len, "stage": stage,
    }


# ---------------------------------------------------------------------------
# Accuracy axis (fake-quant forward vs fp32 oracle)
# ---------------------------------------------------------------------------

def make_eval_batch(cfg: ModelConfig, batch: int = 2, seq: int = 32,
                    seed: int = 0) -> dict:
    """Calibration/eval token batch for the accuracy proxy."""
    key = jax.random.PRNGKey(seed)
    out = {"tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab)}
    if cfg.encdec:
        out["enc_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), (batch, seq, cfg.d_model)) * 0.1
    if cfg.frontend == "vision":
        out["embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2),
            (batch, cfg.n_frontend_tokens, cfg.d_model)) * 0.1
    return out


def oracle_logits(cfg: ModelConfig, batch: dict, seed: int = 0):
    """fp32 logits of the *unquantized* model at the shared init."""
    from repro.models.model import forward, init_lm

    cfg_f = cfg.with_quant(None) if cfg.policy is not None else cfg
    params = init_lm(jax.random.PRNGKey(seed), cfg_f)
    return forward(params, cfg_f, batch["tokens"],
                   embeds=batch.get("embeds"),
                   enc_embeds=batch.get("enc_embeds"))


def accuracy_proxy(cfg: ModelConfig, policy, batch: dict,
                   ref_logits=None, seed: int = 0) -> dict:
    """Calibrated fake-quant forward error vs the fp32 oracle.

    Init under the policy shares the float weights with the oracle (the
    quantizer state is derived from the weights, not the PRNG), so the
    error is purely the policy's quantization noise.  Returns the scalar
    ``error`` (relative L1 on logits) plus top-1 agreement and KL — the
    accuracy coordinate of one search point.
    """
    from repro.models.model import forward, init_lm
    from repro.quant.qat import calibrate_model

    cfg_q = cfg.with_quant(policy)
    params = init_lm(jax.random.PRNGKey(seed), cfg_q)
    params = calibrate_model(params, cfg_q, batch)
    logits = forward(params, cfg_q, batch["tokens"],
                     embeds=batch.get("embeds"),
                     enc_embeds=batch.get("enc_embeds"))
    if ref_logits is None:
        ref_logits = oracle_logits(cfg, batch, seed)
    lf = ref_logits.astype(jnp.float32)
    lq = logits.astype(jnp.float32)
    rel = float(jnp.mean(jnp.abs(lq - lf)) /
                jnp.maximum(jnp.mean(jnp.abs(lf)), 1e-12))
    top1 = float(jnp.mean((jnp.argmax(lq, -1) == jnp.argmax(lf, -1))
                          .astype(jnp.float32)))
    pf = jax.nn.softmax(lf, -1)
    kl = float(jnp.mean(jnp.sum(
        pf * (jax.nn.log_softmax(lf, -1) - jax.nn.log_softmax(lq, -1)), -1)))
    return {"error": rel, "top1_agreement": top1, "kl": kl}


# ---------------------------------------------------------------------------
# Round trip: searched policy -> calibrate -> export -> kernel serving
# ---------------------------------------------------------------------------

def roundtrip_report(cfg: ModelConfig, policy, batch: dict,
                     seed: int = 0, max_new_tokens: int = 6) -> dict:
    """Prove a searched policy is servable on the integer path.

    calibrate -> ``export_quantized`` -> (a) GEMM-level oracle-vs-pallas
    bit parity on an exported PSUM-quantized layer, (b) greedy decode
    parity through ``ServingEngine`` pinned to each backend.
    """
    from repro.core import DeployedQuantState
    from repro.exec import PallasBackend, backend_parity_check
    from repro.models.model import init_lm
    from repro.quant.export import export_quantized
    from repro.quant.qat import calibrate_model
    from repro.serving import Request, ServingEngine

    cfg_q = cfg.with_quant(policy)
    params = init_lm(jax.random.PRNGKey(seed), cfg_q)
    params = calibrate_model(params, cfg_q, batch)
    deploy, export_rep = export_quantized(params)

    # (a) bit parity on a deployed linear — prefer a PSUM-quantized one
    # (the APSQ kernel path), fall back to plain W8A8 codes.
    def find_deployed(tree, require_psum):
        if isinstance(tree, DeployedQuantState):
            ok = tree.w_codes.ndim == 2 and (
                tree.psum_exps is not None or not require_psum)
            return tree if ok else None
        if isinstance(tree, dict):
            for v in tree.values():
                hit = find_deployed(v, require_psum)
                if hit is not None:
                    return hit
        return None

    report: dict = {"n_exported_layers": len(export_rep)}
    dq = (find_deployed(deploy, True) or find_deployed(deploy, False))
    if dq is not None:
        k = int(dq.w_codes.shape[0])
        x = jax.random.normal(jax.random.PRNGKey(seed + 1), (4, k))
        _, times, bit_equal = backend_parity_check(dq, x)
        report["gemm_parity"] = {
            "layer": dq.name, "bit_equal": bool(bit_equal),
            **{f"{n}_us": round(t, 1) for n, t in times.items()}}

    # (b) greedy decode parity: oracle vs interpreted pallas, token for
    # token
    prompt = np.asarray(batch["tokens"])[0, :8].astype(np.int64)
    decodes = {}
    for backend in ("oracle", PallasBackend(interpret=True)):
        eng = ServingEngine(deploy, cfg_q, max_batch=1, cache_len=64,
                            prefill_chunk=8, backend=backend)
        done = eng.run([Request(uid=0, tokens=prompt,
                                max_new_tokens=max_new_tokens)])
        decodes[eng.backend.name] = list(done[0].out)
    report["decode"] = decodes
    report["serving_parity"] = decodes["oracle"] == decodes["pallas"]
    report["ok"] = bool(report.get("serving_parity")
                        and report.get("gemm_parity", {}).get("bit_equal",
                                                             True))
    return report
