"""Serving engines: prefill/decode split with continuous batching.

Two engines share the Request/step/run host API:

``ServingEngine`` — dense float KV caches, one [max_batch, cache_len]
cache per attention layer.  The simple reference path.

``PagedServingEngine`` — the production path: every attention layer's
cache is a pool of fixed-size INT8 pages (``repro.serving.paged_cache``)
with per-(slot, kv-head) power-of-two scales, shared by all request
slots through a page table.  Pages are allocated on demand and reclaimed
on finish/eviction by the host-side ``repro.serving.scheduler``; the
attention read path is the ``kv_attention`` exec op family, i.e. the
``kernels/int8_kv_attention`` flash-decode Pallas kernel or its jnp
oracle, whichever backend the engine names.  Because a slot's running
exponents depend only on its own tokens, greedy decodes are
token-identical regardless of which
other requests share the pool — admission and eviction mid-decode never
change anyone's output.

Production pattern (vLLM-style, TPU-adapted):
  * fixed-shape request slots (``max_batch``) so every decode step hits the
    same compiled executable — no shape churn;
  * chunked parallel prefill (paged engine): prompts are processed in
    chunks of up to ``prefill_chunk`` tokens, one batched forward per
    chunk (``forward_paged_chunk``) — every non-attention GEMM runs once
    at m=chunk and attention attends the whole chunk against the paged
    cache with an in-chunk causal mask.  The chunk's quantized KV goes
    through the same per-token bump-rescale recurrence as decode, so the
    resulting cache (codes AND exponents) is bit-identical to the old
    token-by-token scan.  Chunk sizes are snapped to powers of two, so
    the chunk body compiles for at most log2(prefill_chunk)+1 shapes;
  * token-budget steps: each engine heartbeat spends up to
    ``prefill_token_budget`` prompt tokens (default: ``prefill_chunk *
    max_batch`` — one chunk per slot) on mid-prefill slots before running
    the decode batch, so prefill of long prompts interleaves with
    in-flight decodes instead of stalling them.  Raise ``prefill_chunk``
    for prompt-heavy loads — TTFT drops roughly with the chunk count per
    prompt; lower the budget when decode-latency jitter matters more
    than TTFT (a budget of one chunk serializes prompt admission across
    slots and multiplies TTFT by the mid-prefill slot count);
  * fused decode horizon: every heartbeat runs up to ``decode_horizon``
    (pow2, default 8 on the paged engine) decode steps inside ONE jitted
    ``lax.scan`` macro-step — greedy/sampled token selection, per-slot
    EOS and max-token detection, position advance, and the paged-KV
    writes all stay on device, and the host syncs once per macro-step to
    drain a [B, H] token block instead of once per token.  The scanned
    body advances ALL decoding slots together (per-slot position
    vector); slots still mid-prefill — or finishing mid-horizon — ride
    along masked out: zeroed page-table rows land their writes on the
    null page and their per-slot state reverts each scan step, so H
    fused steps are token- and KV-bit-identical to H single-step calls.
    Raise ``decode_horizon`` when decode is dispatch-bound (many small
    kernel launches per token — the regime every BENCH_serving cell
    measured pre-fusion); keep it at 1 when the page pool runs tight
    (horizon page reservations add transient pressure, though budgets
    shrink rather than preempt) or when a strict per-token SLO on the
    tokens right after TTFT matters — the first decode token of a
    request is only visible to the host after its whole macro-step;
  * finished slots are freed and re-usable; requests stop on
    ``max_new_tokens``, cache capacity, or their ``eos_token``;
  * eviction (paged engine): when the page pool runs dry mid-decode the
    latest-admitted request is preempted and requeued at the front; on
    re-admission it re-prefills over prompt + generated tokens, which is
    bit-identical to the uninterrupted decode because the chunked prefill
    matches the decode recurrence bit-for-bit.  A prefill that cannot
    grow its next chunk's pages (and has no later-admitted victim to
    evict) simply pauses at the chunk boundary, keeping its slot and
    pages, and resumes from ``pos`` next heartbeat;
  * standalone INT8 KV cache helpers (APSQ-style PO2 scales applied to
    whole cache tensors — ``quantize_kv``/``dequantize_kv``).

Integer serving (the calibrate -> export -> kernel-serving flow):

    params = calibrate_model(qat_params, cfg, batch)     # capture-based
    eng = ServingEngine.from_exported(params, cfg, backend="auto")
    eng.run([Request(uid=0, tokens=prompt)])

``from_exported`` exports every quantized linear to INT8 codes + PO2
shift exponents and the engine executes them through the ``repro.exec``
backend registry: ``backend="pallas"`` runs the compiled Pallas kernels
(the chip path; it fails without a TPU), ``PallasBackend(interpret=True)``
the same kernels in the Pallas interpreter (what the CPU tests run),
``backend="oracle"`` the jnp reference semantics, and ``backend="auto"``
(default) pallas on TPU, the oracle elsewhere — a convenience for CPU
tests and examples.  Greedy decodes are token-for-token identical across
backends.

Pallas launch geometry resolves per shape class through the block
autotuner (``repro.kernels.autotune``): decode steps (M=1) take the
single-row fast path, prefill chunks get large tiles, and stacked MoE
expert banks run as ONE fused grid over all experts.  Tuned winners in
the table ``$REPRO_AUTOTUNE_CACHE`` names apply automatically; pass
``backend=PallasBackend(block_overrides={"decode_m1": BlockConfig(1,
512)})`` to pin blocks for a shape class explicitly.

The engine is host-driven (python around two jit'd functions) — the
launcher's ``serve.py`` runs it; the dry-run lowers ``serve_step`` from
``repro.launch.dryrun`` directly.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig
from repro.models.model import (
    batch_state_axes,
    decode_horizon_paged,
    decode_step,
    decode_step_paged,
    forward_paged_chunk,
    init_decode_state,
    init_paged_decode_state,
    paged_state_axes,
)
from .paged_cache import NULL_PAGE, page_span


def _check_horizon(h) -> int:
    h = int(h)
    if h < 1 or (h & (h - 1)):
        raise ValueError(f"decode_horizon must be a power of two >= 1, "
                         f"got {h}")
    return h


@dataclasses.dataclass
class Request:
    """One generation request.  The paged engine stamps its lifecycle on
    the host clock (``time.perf_counter``): ``t_submit`` when it is
    queued, ``t_admit`` when it first gets a slot (a preemption does not
    reset it) and ``t_prefilled`` when its prompt's first token reached
    the host.  [t_submit, t_admit] is its queue wait, [t_admit,
    t_prefilled] its prefill."""
    uid: int
    tokens: np.ndarray            # prompt
    max_new_tokens: int = 32
    eos_token: int | None = None  # stop when this token is generated
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float | None = None
    t_admit: float | None = None
    t_prefilled: float | None = None

    def hit_eos(self) -> bool:
        return (self.eos_token is not None and len(self.out) > 0
                and self.out[-1] == self.eos_token)


# ---------------------------------------------------------------------------
# INT8 KV cache (beyond-paper, APSQ-style PO2 scales)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array):
    """Per-(batch, head) PO2-scale INT8 codes for KV cache pages.

    x: [B, S, H, hd].  Scales are powers of two so dequant is a shift —
    the same hardware argument the paper makes for PSUM scales (§II-B).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 3), keepdims=True)
    exp = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-8) / 127.0))
    scale = jnp.exp2(exp)
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def dequantize_kv(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)


# Slot-axis trees live next to the state builders in ``models.model``
# (``batch_state_axes`` / ``paged_state_axes``); these aliases keep the
# engine's historical private names working for downstream code.
_batch_axes_tree = batch_state_axes


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, *, max_batch: int = 8,
                 cache_len: int = 1024, prefill_chunk: int = 64,
                 decode_horizon: int = 1,
                 mesh=None, greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0, backend="auto"):
        from repro.exec import get_backend
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.prefill_chunk = prefill_chunk
        # Fused decode horizon (pow2): up to this many decode steps run
        # inside one jitted lax.scan per step() heartbeat, with a single
        # host sync draining the [B, H] token block.  Default 1 keeps the
        # reference engine on the classic one-token heartbeat; the paged
        # engine defaults to 8 (see PagedServingEngine).
        self.decode_horizon = _check_horizon(decode_horizon)
        self.mesh = mesh
        self.greedy = greedy
        self.temperature = temperature
        self.rng = jax.random.PRNGKey(seed)
        # Integer execution backend for deployed params (repro.exec):
        # "pallas" (compiled kernels, fails off TPU) or "oracle", or an
        # ExecBackend instance such as PallasBackend(interpret=True) for
        # the CPU tests.  "auto" (default) picks pallas on TPU and the
        # oracle elsewhere: a convenience for CPU tests and examples, not
        # the chip path, which names "pallas" so a missing chip cannot
        # pass unnoticed.  Float / fake-quant params ignore it.
        self.backend = get_backend(backend)

        self.state = init_decode_state(cfg, max_batch, cache_len)
        self.pos = np.zeros(max_batch, np.int32)      # next position per slot
        # Device-resident copy of ``pos``: decode advances it functionally
        # inside the jitted scan; the host mirror is only re-uploaded when
        # host code writes it (admission / prefill), not every step.
        self._pos_dev = None
        self._pos_dirty = True
        self.slots: list = [None] * max_batch
        self.reset_counters()
        self._decode = jax.jit(self._decode_impl, static_argnums=(0,))
        self._prefill = jax.jit(self._prefill_impl)

    def reset_counters(self) -> None:
        """Zero the dispatch counters (benchmarks call this after warmup
        so compile time stays out of the measured window)."""
        self.decode_dispatches = 0     # jitted decode launches
        self.decode_device_steps = 0   # scan steps across those launches
        self.horizon_hist: dict[int, int] = {}  # scan length -> launches

    @classmethod
    def from_exported(cls, params, cfg: ModelConfig, *, policy=None, **kw):
        """Serve the integer deployment path: export the calibrated QAT
        params (INT8 weight codes + PO2 shift exponents per layer, see
        ``repro.quant.export``) and run every projection GEMM through the
        ``kernels/apsq_matmul`` integer semantics inside decode.  The
        ``backend=`` knob picks the executor: ``pallas`` (compiled
        kernels), ``oracle``, or ``auto`` (kernel on TPU, oracle
        elsewhere; for CPU tests)."""
        from repro.quant.export import export_quantized
        deploy, _ = export_quantized(params, policy)
        return cls(deploy, cfg, **kw)

    # -- jitted bodies ------------------------------------------------------

    def _prefill_impl(self, params, state, tokens, slot, length):
        """Prefill one slot.  tokens: [1, Lpad] (bucket-padded); slot and
        length are traced scalars.  Steps the decode path token-by-token
        (identical cache layout to decode); state updates beyond ``length``
        are masked out so padding never pollutes recurrent state."""
        cfg = self.cfg
        fresh = init_decode_state(cfg, 1, self.cache_len)

        def body(carry, tok_pos):
            st, lg = carry
            tok, pos = tok_pos
            lg2, st2 = decode_step(params, cfg, st, tok[None, None], pos,
                                   mesh=self.mesh, backend=self.backend)
            valid = pos < length
            st = jax.tree.map(lambda a, b: jnp.where(valid, b, a), st, st2)
            lg = jnp.where(pos == length - 1, lg2[:, -1].astype(lg.dtype), lg)
            return (st, lg), ()

        lg0 = jnp.zeros((1, cfg.vocab), jnp.float32)
        (st, lg), _ = jax.lax.scan(
            body, (fresh, lg0),
            (tokens[0], jnp.arange(tokens.shape[1], dtype=jnp.int32)))
        axes = _batch_axes_tree(state, self.cfg.scan_layers)
        new_state = jax.tree.map(
            lambda full, s, ax: jax.lax.dynamic_update_slice_in_dim(
                full, s.astype(full.dtype), slot, axis=ax),
            state, st, axes)
        return new_state, lg

    def _decode_impl(self, h, params, state, tokens, pos, active, budget,
                     remaining, eos, rng):
        """``h`` fused decode steps for all slots in ONE ``lax.scan``.

        tokens [B, 1]; pos/budget/remaining/eos [B] int32; active [B]
        bool.  Sampling, EOS / token-budget detection and position
        advance all happen on device; the host drains the [B, h] token
        block once per call.  A slot that finishes (EOS or last token)
        mid-horizon keeps riding the batch with its position frozen and
        token 0 fed, exactly like an empty slot, so ``h`` fused steps
        emit the same tokens as ``h`` single-step calls.  ``eos`` is -1
        for slots without a stop token.  Returns (tok_block [B, h],
        emitted [B, h] prefix mask, state, pos, rng)."""
        cfg = self.cfg
        axes = _batch_axes_tree(state, cfg.scan_layers)
        temp = jnp.maximum(self.temperature, 1e-6)

        def one(st, tok, ps):
            # vmap strips the slot axis; reinsert a size-1 batch dim.
            st1 = jax.tree.map(lambda a, ax: jnp.expand_dims(a, ax),
                               st, axes)
            lg, st2 = decode_step(params, cfg, st1, tok[None], ps,
                                  mesh=self.mesh, backend=self.backend)
            st2 = jax.tree.map(lambda a, ax: jnp.squeeze(a, ax), st2, axes)
            return lg[0, -1], st2

        def body(carry, _):
            st, tok, ps, act, bud, rem, key = carry
            on = act & (bud > 0)
            logits, st2 = jax.vmap(
                one, in_axes=(axes, 0, 0), out_axes=(0, axes))(st, tok, ps)
            logits = logits / temp
            key, sub = jax.random.split(key)
            if self.greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(sub, logits,
                                             axis=-1).astype(jnp.int32)
            rem2 = jnp.where(on, rem - 1, rem)
            fin = on & ((nxt == eos) | (rem2 <= 0))
            tok2 = jnp.where(on, jnp.where(fin, 0, nxt), tok[:, 0])[:, None]
            carry2 = (st2, tok2, ps + on.astype(ps.dtype), act & ~fin,
                      bud - on.astype(bud.dtype), rem2, key)
            return carry2, (nxt, on)

        carry = (state, tokens, pos, active, budget, remaining, rng)
        (st, _, ps, _, _, _, key), (toks, ons) = jax.lax.scan(
            body, carry, None, length=h)
        return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(ons, 0, 1),
                st, ps, key)

    # -- host API -----------------------------------------------------------

    def add_request(self, req: Request) -> bool:
        """Prefill into a free slot; False if engine full."""
        try:
            slot = self.slots.index(None)
        except ValueError:
            return False
        L = int(len(req.tokens))
        pad = -L % self.prefill_chunk
        toks = np.pad(np.asarray(req.tokens, np.int32), (0, pad))[None]
        self.state, logits = self._prefill(
            self.params, self.state, jnp.asarray(toks),
            jnp.asarray(slot, jnp.int32), jnp.asarray(L, jnp.int32))
        self.slots[slot] = req
        self.pos[slot] = L
        self._pos_dirty = True
        req.out.append(int(jnp.argmax(logits[0])))
        if len(req.out) >= req.max_new_tokens or req.hit_eos():
            req.done = True  # finished on the prefill token; step() sweeps
        return True

    def step(self) -> list:
        """One decode macro-step (up to ``decode_horizon`` tokens per
        slot) for every active slot; returns finished requests."""
        finished = []
        for i, r in enumerate(self.slots):  # finished at admission (eos etc.)
            if r is not None and r.done:
                finished.append(r)
                self.slots[i] = None
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return finished
        B = self.max_batch
        tokens = np.zeros((B, 1), np.int32)
        mask = np.zeros(B, np.bool_)
        bud = np.zeros(B, np.int32)
        rem = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        for i in active:
            r = self.slots[i]
            tokens[i, 0] = r.out[-1]
            mask[i] = True
            rem[i] = r.max_new_tokens - len(r.out)
            # Never scan past the cache: the last writable position is
            # cache_len - 2 (matching the old per-step pos bound check).
            bud[i] = min(self.decode_horizon,
                         self.cache_len - 1 - int(self.pos[i]))
            if r.eos_token is not None:
                eos[i] = r.eos_token
        # Snap the scan length to the largest useful step count (pow2 so
        # the jit compiles at most log2(decode_horizon)+1 variants).
        h = max(1, max(int(min(bud[i], rem[i])) for i in active))
        h = 1 << (h - 1).bit_length()
        if self._pos_dirty:
            self._pos_dev = jnp.asarray(self.pos)
            self._pos_dirty = False
        blk, em, self.state, self._pos_dev, self.rng = self._decode(
            h, self.params, self.state, jnp.asarray(tokens), self._pos_dev,
            jnp.asarray(mask), jnp.asarray(bud), jnp.asarray(rem),
            jnp.asarray(eos), self.rng)
        blk = np.asarray(blk)
        em = np.asarray(em)
        self.decode_dispatches += 1
        self.decode_device_steps += h
        self.horizon_hist[h] = self.horizon_hist.get(h, 0) + 1
        for i in active:
            r = self.slots[i]
            for t in range(h):
                if not em[i, t]:
                    break
                r.out.append(int(blk[i, t]))
                self.pos[i] += 1    # device pos advanced identically
            if (len(r.out) >= r.max_new_tokens
                    or self.pos[i] >= self.cache_len - 1
                    or r.hit_eos()):
                r.done = True
                finished.append(r)
                self.slots[i] = None
        return finished

    def run(self, requests: list) -> list:
        """Continuous batching until every request completes."""
        pending = list(requests)
        done: list = []
        while pending or any(s is not None for s in self.slots):
            while pending and self.add_request(pending[0]):
                pending.pop(0)
            done.extend(self.step())
        return done


# ---------------------------------------------------------------------------
# Paged engine (continuous batching over the INT8 page pool)
# ---------------------------------------------------------------------------

_paged_axes_tree = paged_state_axes


class PagedServingEngine:
    """Continuous-batching engine over the paged INT8 KV cache.

    Same host API as ``ServingEngine`` (``Request`` in, ``step``/``run``
    out) but requests are queued through the ``repro.serving.scheduler``:
    admission waits for a slot + the FIRST prefill chunk's pages, prompts
    prefill chunk-by-chunk under a per-step token budget (interleaved
    with the decode batch), decode grows each slot's page list on demand,
    and a dry pool preempts the latest-admitted request (requeued at the
    front; resume re-prefills prompt + generated and is bit-identical).

    Knobs (see the module docstring for when to turn them):
      * ``prefill_chunk``        — max tokens per prefill forward; the
        chunk rides the m axis of every GEMM and the query-row axis of
        the attention kernel.  Raise it to cut TTFT on prompt-heavy
        loads; 1 degenerates to the old token-by-token prefill.
      * ``prefill_token_budget`` — prompt tokens spent per ``step()``
        across all mid-prefill slots (default ``prefill_chunk *
        max_batch``: every slot advances one chunk per heartbeat).
        Lower it to bound decode-step latency jitter at the cost of
        slower prompt-backlog draining (and so higher TTFT).
      * ``decode_horizon``       — fused decode steps per heartbeat
        (pow2, default 8): one jitted scan emits up to H tokens per slot
        with a single host sync.  Raise it when decode is
        dispatch-bound; 1 restores the classic per-token heartbeat
        (tight page pools, strict per-token SLO).  ``_ensure_capacity``
        pre-reserves each slot's pages over [pos, pos+H) and shrinks the
        slot's budget instead of preempting when the pool is tight.

    Tracing: ``step`` writes host spans named ``engine.*`` (admission,
    prefill with its first-token syncs, page capacity, decode launch and
    drain, token bookkeeping) that a ``jax.profiler`` session records and
    that cost nothing without one; the jitted programs carry
    ``jax.named_scope`` paths (``decode``, ``prefill``, ``attn``,
    ``mlp``, ``kv_update``, ``kv_attention``, ``apsq_gemm``, ...) in
    their ops' metadata; each ``Request`` carries its lifecycle stamps.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_batch: int = 8,
                 page_size: int = 16, n_pages: int = 128,
                 max_pages_per_slot: int | None = None,
                 prefill_chunk: int = 16,
                 prefill_token_budget: int | None = None,
                 decode_horizon: int = 8,
                 mesh=None, greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0, backend="auto",
                 wire: str = "int8"):
        from repro.exec import get_backend
        from .scheduler import Scheduler
        if any(k == "local" for k in cfg.block_pattern) or cfg.softcap:
            raise NotImplementedError(
                "paged serving covers full-attention (+ recurrent) "
                "layers only — no sliding-window / softcap yet")
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.page_size = page_size
        self.prefill_chunk = max(int(prefill_chunk), 1)
        # Default budget: every slot can advance one full chunk per step.
        # A budget of one chunk TOTAL would serialize prompt admission
        # across slots and multiply TTFT by the mid-prefill slot count.
        self.prefill_token_budget = max(
            int(prefill_token_budget) if prefill_token_budget
            else self.prefill_chunk * max_batch, 1)
        # Fused decode horizon (pow2): up to this many decode steps per
        # heartbeat run inside ONE jitted lax.scan, with a single host
        # sync draining the [B, H] token block.  _ensure_capacity
        # pre-reserves each slot's pages over [pos, pos + H) and shrinks
        # the slot's budget (never preempting) when the pool is tight.
        # 1 degenerates to the classic one-token heartbeat.
        self.decode_horizon = _check_horizon(decode_horizon)
        self.mesh = mesh
        self.greedy = greedy
        self.temperature = temperature
        self.rng = jax.random.PRNGKey(seed)
        self.backend = get_backend(backend)

        self.state = init_paged_decode_state(cfg, max_batch,
                                             page_size=page_size,
                                             n_pages=n_pages)
        # Multi-device integer serving: wrap the backend in the mesh-
        # parallel executor (repro.dist.tp plans the per-layer shard axis
        # from Algorithm-1 semantics), commit the exported code banks to
        # their shards, and shard the KV pools over kv-heads.  The plan's
        # analytic wire report lands on ``self.shard_plan`` for
        # ``benchmarks/dist_bench.py``.  ``wire="fp32"`` keeps identical
        # outputs but full-precision collectives (parity debugging).
        self.shard_plan = None
        if mesh is not None:
            from repro.dist.tp import shard_deployed, shard_paged_state
            from repro.exec import ShardedBackend
            if not isinstance(self.backend, ShardedBackend):
                self.backend = ShardedBackend(mesh=mesh, inner=self.backend,
                                              wire=wire)
            self.params, self.shard_plan = shard_deployed(params, mesh)
            self.state, attn_plans = shard_paged_state(self.state, cfg, mesh)
            self.shard_plan.update(attn_plans)
        self.sched = Scheduler(max_slots=max_batch, n_pages=n_pages,
                               page_size=page_size,
                               max_pages_per_slot=max_pages_per_slot,
                               admit_chunk=self.prefill_chunk)
        self.pos = np.zeros(max_batch, np.int32)      # next position per slot
        # Device-resident pos (see ServingEngine): the fused decode scan
        # advances positions functionally on device; the host mirror is
        # re-uploaded only after host writes (admission, prefill chunks).
        self._pos_dev = None
        self._pos_dirty = True
        # Mid-prefill bookkeeping: slot -> full resume stream (prompt +
        # pre-preemption output).  While a slot is here, ``pos[slot]`` is
        # its prefilled_len — the last completed chunk boundary.
        self._mid_prefill: dict[int, np.ndarray] = {}
        # The attention kernel's KV tile over a slot's gathered pages, as
        # the decode launch resolves it (``kv_tile``): the tile counters
        # below replay the kernel's skip of tiles past each slot's length.
        seq = self.sched.table.shape[1] * page_size
        self._kv_tile = self.backend.kv_tile(
            seq, cfg.hd, cfg.n_heads // cfg.n_kv_heads)
        self._kv_tiles = seq // self._kv_tile
        self.reset_counters()
        self._decode = jax.jit(self._decode_impl, static_argnums=(0,))
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl)

    def reset_counters(self) -> None:
        """Zero dispatch counters (benchmarks call this after the warmup
        request so compile time stays out of the window)."""
        self.prefill_tokens = 0      # prompt tokens pushed through chunks
        self.prefill_dispatches = 0  # prefill chunk launches
        self.decode_dispatches = 0   # fused decode launches
        self.decode_device_steps = 0  # scan steps across those launches
        self.horizon_hist: dict[int, int] = {}  # scan length -> launches
        # Decode attention's KV tiles, per slot and scan step (a tile
        # stands for that tile of every KV head in every attention layer):
        # those the grid launches, and those holding a live position,
        # which the kernel fetches and computes.  A slot not decoding at a
        # step attends over length 1.
        self.kv_tiles_launched = 0
        self.kv_tiles_live = 0

    @classmethod
    def from_exported(cls, params, cfg: ModelConfig, *, policy=None, **kw):
        """Integer serving end-to-end: INT8 weights through the APSQ GEMM
        kernel *and* INT8 KV pages through the flash-decode kernel."""
        from repro.quant.export import export_quantized
        deploy, _ = export_quantized(params, policy)
        return cls(deploy, cfg, **kw)

    # -- jitted bodies ------------------------------------------------------

    def _prefill_chunk_impl(self, params, state, tokens, slot, start,
                            table_row):
        """Prefill ONE chunk of one slot against the shared page pools.

        tokens [1, C] (every token valid — chunk sizes are exact);
        ``slot``/``start`` traced scalars; ``table_row`` [1, n_max].  One
        batched ``forward_paged_chunk`` whose paged-cache writes replay
        the per-token bump-rescale recurrence, so the pools and running
        exponents end bit-identical to C single-token decode steps — a
        resumed (preempted) request recomputes exactly the cache it
        lost.  ``start == 0`` (first chunk) resets the slot's per-slot
        leaves (exponents, recurrent states) left by a prior occupant."""
        cfg = self.cfg
        axes = _paged_axes_tree(state, cfg.scan_layers)
        fresh = init_paged_decode_state(cfg, 1, page_size=self.page_size,
                                        n_pages=1)  # pools unused
        sub = jax.tree.map(
            lambda full, fr, ax: full if ax == -1 else jnp.where(
                start == 0, fr,
                jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=ax)),
            state, fresh, axes)
        lg, st = forward_paged_chunk(
            params, cfg, sub, tokens,
            jnp.full((1,), start, jnp.int32), table_row,
            mesh=self.mesh, backend=self.backend)
        new_state = jax.tree.map(
            lambda full, s, ax: s if ax == -1
            else jax.lax.dynamic_update_slice_in_dim(
                full, s.astype(full.dtype), slot, axis=ax),
            state, st, axes)
        return new_state, lg[:, -1]

    def _decode_impl(self, h, params, state, tokens, pos, table, active,
                     budget, remaining, eos, rng):
        """``h`` fused decode steps for all slots in ONE ``lax.scan`` —
        the scanned body is ``decode_step_paged`` with the PR-8 masking
        applied per scan step: slots that are inactive (empty or
        mid-prefill), finished mid-horizon (EOS / last token), or out of
        page budget carry all-null table rows (their garbage writes land
        on the masked null page) and have their per-slot leaves — running
        exponents, recurrent states — reverted, so riding along in the
        batch cannot disturb a slot that is not decoding.  See
        ``models.model.decode_horizon_paged`` for the contract."""
        return decode_horizon_paged(
            params, self.cfg, state, tokens, pos, table,
            horizon=h, active=active, budget=budget, remaining=remaining,
            eos=eos, greedy=self.greedy, temperature=self.temperature,
            rng=rng, mesh=self.mesh, backend=self.backend)

    # -- host API -----------------------------------------------------------

    def add_request(self, req: Request) -> bool:
        """Queue a request (admission happens inside ``step``)."""
        self.sched.submit(req)
        return True

    def _admit(self) -> None:
        """Admit queued requests while a slot + the FIRST chunk's pages
        are free.  Admission only books the slot; the prompt itself runs
        chunk-by-chunk in ``_prefill_step`` (later pages grow per chunk)."""
        while True:
            got = self.sched.admit_next()
            if got is None:
                return
            slot, req, resume = got
            self._mid_prefill[slot] = np.asarray(resume, np.int32)
            self.pos[slot] = 0
            self._pos_dirty = True

    def _preempt(self, slot: int) -> None:
        """Preempt a slot (decoding or mid-prefill), releasing its pages.
        Its request requeues at the front; a mid-prefill victim loses its
        chunk progress and re-prefills from scratch on re-admission."""
        self._mid_prefill.pop(slot, None)
        self.sched.preempt(slot)

    def _grow_range(self, slot: int, start: int, end: int) -> bool:
        """Ensure pages exist for positions [start, end).  A dry pool
        evicts only slots admitted LATER than ``slot`` (so prefill never
        steals from older work); False means pause at this chunk
        boundary — the slot keeps its pages and resumes next step."""
        for p in page_span(start, end, self.page_size):
            while not self.sched.grow(slot, p):
                victim = self.sched.evict_candidate(exclude=slot)
                if victim is None or (self.sched._admitted_at[victim]
                                      <= self.sched._admitted_at[slot]):
                    return False
                self._preempt(victim)
        return True

    def _prefill_step(self) -> None:
        """Advance mid-prefill slots, oldest first, spending at most
        ``prefill_token_budget`` prompt tokens.  Chunk sizes are powers
        of two <= ``prefill_chunk`` (so every chunk is fully valid — no
        pad masking — and the chunk body compiles for at most
        log2(prefill_chunk)+1 shapes).  The final chunk's logits produce
        the request's first output token, exactly like a decode step, and
        reaches the host right after that chunk (stamping
        ``t_prefilled``)."""
        budget = self.prefill_token_budget
        order = sorted(self._mid_prefill,
                       key=lambda s: self.sched._admitted_at[s])
        for s in order:
            if s not in self._mid_prefill:            # evicted by a grow
                continue
            resume = self._mid_prefill[s]
            while budget > 0 and int(self.pos[s]) < len(resume):
                done = int(self.pos[s])
                c = min(self.prefill_chunk, len(resume) - done, budget)
                c = 1 << (c.bit_length() - 1)         # pow2 chunk sizes
                if not self._grow_range(s, done, done + c):
                    return                            # pool dry: pause
                self.state, logits = self._prefill_chunk(
                    self.params, self.state,
                    jnp.asarray(resume[done:done + c][None]),
                    jnp.asarray(s, jnp.int32), jnp.asarray(done, jnp.int32),
                    jnp.asarray(self.sched.table[s:s + 1]))
                self.prefill_dispatches += 1
                self.prefill_tokens += c
                self.pos[s] = done + c
                self._pos_dirty = True
                budget -= c
                if done + c == len(resume):           # prompt fully cached
                    req = self.sched.slots[s]
                    req.out.append(int(jnp.argmax(logits[0])))
                    if req.t_prefilled is None:
                        req.t_prefilled = time.perf_counter()
                    del self._mid_prefill[s]
                    if len(req.out) >= req.max_new_tokens or req.hit_eos():
                        req.done = True               # swept by step()
            if budget <= 0:
                return

    def _ensure_capacity(self, horizon: int = 1):
        """Grow each decoding slot's pages for its next write plus — pool
        permitting — the rest of its decode horizon.

        The FIRST page (the next write position) keeps the old guarantee:
        a dry pool preempts latest-admitted requests until it fits.  The
        horizon extension over ``[pos + 1, pos + horizon)`` is
        opportunistic (``Scheduler.grow_span`` never evicts): when the
        pool is tight the slot's macro-step budget simply shrinks — down
        to the single guaranteed token — instead of preempting
        co-resident work.  Positions past a slot's budget stay masked in
        the scan, so partially covered horizons are safe.

        Returns ``(finished, budgets)``: requests finished by running out
        of page budget, and per-slot device-step budgets [max_batch]
        int32 (0 for empty / mid-prefill slots, else >= 1)."""
        finished = []
        budgets = np.zeros(self.max_batch, np.int32)
        order = sorted(
            (s for s, r in enumerate(self.sched.slots)
             if r is not None and s not in self._mid_prefill),
            key=lambda s: self.sched._admitted_at[s])
        for s in order:                               # oldest first
            if self.sched.slots[s] is None:           # evicted below
                continue
            pos = int(self.pos[s])
            if pos >= self.sched.capacity_tokens:
                r = self.sched.finish(s)              # page budget exhausted
                r.done = True
                finished.append(r)
                continue
            guaranteed = True
            while not self.sched.grow(s, pos):
                victim = self.sched.evict_candidate()
                if victim is None or victim == s:
                    if victim == s:                   # newest = itself
                        self._preempt(s)
                        guaranteed = False
                        break
                    raise RuntimeError("page pool dry with no evictable slot")
                self._preempt(victim)
            if not guaranteed:
                continue
            r = self.sched.slots[s]
            want = max(1, min(horizon, self.sched.capacity_tokens - pos,
                              r.max_new_tokens - len(r.out)))
            # End of the guaranteed page, then extend page by page.
            covered = min(pos + want,
                          (pos // self.page_size + 1) * self.page_size)
            if pos + want > covered:
                covered = min(pos + want, covered + self.sched.grow_span(
                    s, covered, pos + want))
            budgets[s] = covered - pos
        return finished, budgets

    def _admit_and_prefill(self) -> list:
        """Admit + prefill + sweep requests finished on their prefill
        token.  Runs at the top of every step AND again after the decode
        sweep, so a slot freed by a finishing stream starts (and usually
        completes) its successor's prefill in the same heartbeat instead
        of idling until the next one — under slot contention that saves
        one full decode step of TTFT per queued request."""
        with TraceAnnotation("engine.admit"):
            self._admit()
        with TraceAnnotation("engine.prefill"):
            self._prefill_step()
        with TraceAnnotation("engine.sweep"):
            return [self.sched.finish(s)
                    for s, r in enumerate(self.sched.slots)
                    if r is not None and r.done]      # done on prefill token

    def step(self) -> list:
        """One continuous-batching heartbeat: admit (slot + first-chunk
        pages), spend the prefill token budget on mid-prefill slots,
        sweep requests finished on their prefill token, ensure decode
        pages over each slot's horizon (evicting only for the first
        token if dry), then ONE fused decode macro-step — up to
        ``decode_horizon`` tokens per decoding slot inside a single
        jitted scan (mid-prefill slots ride along inert) — drain the
        [B, H] token block, and finally re-admit into any slots the
        decode sweep freed."""
        finished = self._admit_and_prefill()
        with TraceAnnotation("engine.capacity"):
            fin_cap, budgets = self._ensure_capacity(self.decode_horizon)
        finished.extend(fin_cap)
        active = [s for s, r in enumerate(self.sched.slots)
                  if r is not None and s not in self._mid_prefill]
        if not active:
            return finished
        with TraceAnnotation("engine.decode.launch"):
            B = self.max_batch
            tokens = np.zeros((B, 1), np.int32)
            mask = np.zeros(B, np.bool_)
            rem = np.zeros(B, np.int32)
            eos = np.full(B, -1, np.int32)
            for s in active:
                r = self.sched.slots[s]
                tokens[s, 0] = r.out[-1]
                mask[s] = True
                rem[s] = r.max_new_tokens - len(r.out)
                if r.eos_token is not None:
                    eos[s] = r.eos_token
            # Zero the table rows of non-decoding slots: their (garbage)
            # writes land on the null page instead of live cache pages.
            table = np.where(mask[:, None], self.sched.table, NULL_PAGE)
            # Scan just long enough for the biggest per-slot budget,
            # snapped to pow2 (at most log2(decode_horizon)+1 compiled
            # variants).
            h = max(1, max(int(budgets[s]) for s in active))
            h = 1 << (h - 1).bit_length()
            if self._pos_dirty:
                self._pos_dev = jnp.asarray(self.pos)
                self._pos_dirty = False
            blk, em, self.state, self._pos_dev, self.rng = self._decode(
                h, self.params, self.state, jnp.asarray(tokens),
                self._pos_dev, jnp.asarray(table), jnp.asarray(mask),
                jnp.asarray(budgets), jnp.asarray(rem), jnp.asarray(eos),
                self.rng)
        with TraceAnnotation("engine.decode.drain"):
            blk = np.asarray(blk)     # the macro-step's single host sync
            em = np.asarray(em)
        with TraceAnnotation("engine.sweep"):
            self.decode_dispatches += 1
            self.decode_device_steps += h
            self.horizon_hist[h] = self.horizon_hist.get(h, 0) + 1
            # Step t attends over pos + (tokens emitted before t) + 1.
            length = np.where(em, self.pos[:, None] + np.cumsum(em, 1)
                              - em + 1, 1)
            self.kv_tiles_launched += em.size * self._kv_tiles
            self.kv_tiles_live += int(np.sum(-(-length // self._kv_tile)))
            for s in active:
                r = self.sched.slots[s]
                for t in range(h):
                    if not em[s, t]:
                        break
                    r.out.append(int(blk[s, t]))
                    self.pos[s] += 1  # device pos advanced identically
                if len(r.out) >= r.max_new_tokens or r.hit_eos():
                    r.done = True
                    finished.append(self.sched.finish(s))
        if self.sched.waiting:                        # refill freed slots now
            finished.extend(self._admit_and_prefill())
        return finished

    def run(self, requests: list) -> list:
        """Continuous batching until every request completes."""
        for r in requests:
            self.sched.submit(r)
        done: list = []
        while self.sched.waiting or any(
                s is not None for s in self.sched.slots):
            done.extend(self.step())
            self.sched.assert_invariants()
        return done
