"""Paged INT8 KV serving: cache semantics, scheduler invariants, engine parity.

The acceptance bar for the continuous-batching subsystem: greedy outputs
of the batched ``PagedServingEngine`` are token-identical to the
single-stream engine — under admission churn, a dry page pool with
mid-decode eviction, and across exec backends (oracle vs interpret-mode
Pallas).  The host-side scheduler never leaks a slot or a page.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.model import (
    decode_step_paged,
    forward_paged_chunk,
    init_lm,
    init_paged_decode_state,
)
from repro.serving import PagedServingEngine, Request
from repro.serving.paged_cache import (
    EXP_FLOOR,
    NULL_PAGE,
    paged_cache_bytes,
    paged_update_and_attend,
)
from repro.serving.scheduler import PageAllocator, Scheduler

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                  dtype="float32")


def _prompt(n, seed=0):
    return ((np.arange(n) * 7 + seed * 13) % CFG.vocab).astype(np.int32)


def _engine(params, **kw):
    kw.setdefault("backend", "oracle")
    kw.setdefault("prefill_chunk", 8)
    return PagedServingEngine(params, CFG, **kw)


# ---------------------------------------------------------------------------
# Paged cache semantics (device level)
# ---------------------------------------------------------------------------

def _fresh_cache(batch, n_pages, page_size, hkv, hd):
    return {"k_pages": jnp.zeros((n_pages, page_size, hkv, hd), jnp.int8),
            "v_pages": jnp.zeros((n_pages, page_size, hkv, hd), jnp.int8),
            "k_exp": jnp.full((batch, hkv), EXP_FLOOR, jnp.int32),
            "v_exp": jnp.full((batch, hkv), EXP_FLOOR, jnp.int32)}


def test_paged_attend_tracks_fp_reference():
    """Stream tokens through the paged cache; each step's output stays
    within INT8-cache noise of exact fp attention over the same prefix."""
    from repro.kernels.int8_kv_attention import fp_attention_ref
    key = jax.random.PRNGKey(0)
    T, hkv, hd, hq, P = 10, 2, 16, 4, 4
    ks = jax.random.normal(key, (1, T, hkv, hd))
    vs = jax.random.normal(jax.random.fold_in(key, 1), (1, T, hkv, hd))
    qs = jax.random.normal(jax.random.fold_in(key, 2), (T, 1, hq, hd))
    cache = _fresh_cache(1, 8, P, hkv, hd)
    table = jnp.asarray([[1, 2, 3]])      # 3 pages = 12 positions
    for t in range(T):
        out, cache = paged_update_and_attend(
            cache, qs[t], ks[:, t:t + 1], vs[:, t:t + 1],
            jnp.asarray([t], jnp.int32), table, backend="oracle")
        fp = fp_attention_ref(qs[t], ks[:, :t + 1], vs[:, :t + 1],
                              jnp.asarray([t + 1], jnp.int32))
        rel = float(jnp.mean(jnp.abs(out - fp)) /
                    jnp.maximum(jnp.mean(jnp.abs(fp)), 1e-9))
        assert rel < 0.06, (t, rel)
    # running exponents cover the stream and never sit below the floor
    assert int(jnp.min(cache["k_exp"])) > EXP_FLOOR


def test_paged_cache_slot_isolated_from_pool_neighbors():
    """A slot's output depends only on its own tokens: junk written by a
    co-resident slot (different pages, own exponents) changes nothing —
    the property that makes batched decode token-identical."""
    key = jax.random.PRNGKey(1)
    hkv, hd, hq, P = 2, 8, 4, 4
    k1 = jax.random.normal(key, (1, 1, hkv, hd))
    v1 = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, hkv, hd))
    q1 = jax.random.normal(jax.random.fold_in(key, 2), (1, hq, hd))

    solo = _fresh_cache(1, 8, P, hkv, hd)
    out_solo, _ = paged_update_and_attend(
        solo, q1, k1, v1, jnp.asarray([0]), jnp.asarray([[1]]),
        backend="oracle")

    both = _fresh_cache(2, 8, P, hkv, hd)
    k2 = jnp.concatenate([k1, k1 * 100.0])   # neighbor with huge scale
    v2 = jnp.concatenate([v1, v1 * 100.0])
    q2 = jnp.concatenate([q1, q1])
    out_both, _ = paged_update_and_attend(
        both, q2, k2, v2, jnp.asarray([0, 5]), jnp.asarray([[1], [2]]),
        backend="oracle")
    np.testing.assert_array_equal(np.asarray(out_solo[0]),
                                  np.asarray(out_both[0]))


def test_paged_cache_bytes_accounting():
    b = paged_cache_bytes(CFG, n_pages=33, page_size=16, max_batch=8,
                          cache_len=64)
    assert b["n_attn_layers"] == CFG.n_layers
    assert b["int8_paged"] < b["dense_f32"]


# ---------------------------------------------------------------------------
# Scheduler (host level)
# ---------------------------------------------------------------------------

def test_page_allocator_conserved_under_churn():
    alloc = PageAllocator(17)
    rng = np.random.default_rng(0)
    for _ in range(200):
        slot = int(rng.integers(0, 4))
        if rng.random() < 0.6:
            alloc.alloc(slot, int(rng.integers(1, 4)))
        else:
            alloc.release(slot)
        alloc.assert_conserved()
    for s in range(4):
        alloc.release(s)
    alloc.assert_conserved()
    assert alloc.n_free == 16           # every page back, page 0 reserved
    assert NULL_PAGE == 0


def test_scheduler_no_leak_after_evict_and_finish():
    sched = Scheduler(max_slots=2, n_pages=9, page_size=4)
    for i in range(3):
        sched.submit(Request(uid=i, tokens=np.arange(6), max_new_tokens=4))
    s0, r0, _ = sched.admit_next()
    s1, r1, _ = sched.admit_next()
    assert sched.admit_next() is None   # no free slot
    sched.assert_invariants()
    assert sched.grow(s0, 8)            # next page for slot 0
    victim = sched.evict_candidate()
    assert victim == s1                 # latest admitted
    sched.preempt(victim)
    sched.assert_invariants()
    assert sched.waiting[0].uid == r1.uid   # requeued at the front
    assert sched.table[victim].tolist() == [NULL_PAGE] * sched.table.shape[1]
    sched.finish(s0)
    sched.assert_invariants()
    assert sched.alloc.n_free == 8      # all pages back

    # a request that can never fit is rejected up front
    with pytest.raises(ValueError):
        sched.submit(Request(uid=9, tokens=np.arange(40),
                             max_new_tokens=40))


# ---------------------------------------------------------------------------
# Engine parity (the acceptance bar)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _single_stream(params, req_spec):
    eng = _engine(params, max_batch=1, page_size=4, n_pages=32)
    outs = {}
    for uid, toks, n in req_spec:
        r = Request(uid=uid, tokens=toks, max_new_tokens=n)
        eng.run([r])
        outs[uid] = r.out
    return outs


def test_batched_matches_single_stream(params):
    spec = [(i, _prompt(4 + i, seed=i), 6) for i in range(6)]
    single = _single_stream(params, spec)
    eng = _engine(params, max_batch=3, page_size=4, n_pages=32)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert {r.uid: r.out for r in done} == single
    eng.sched.assert_invariants()


def test_eviction_mid_decode_keeps_outputs_identical(params):
    """Pool far too small for 4 concurrent slots: requests get preempted
    mid-decode and resumed, yet every output matches the roomy engine."""
    spec = [(i, _prompt(5 + i, seed=i), 8) for i in range(4)]
    single = _single_stream(params, spec)
    eng = _engine(params, max_batch=4, page_size=4, n_pages=10)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert eng.sched.stats.preempted > 0, "pool was not small enough"
    assert {r.uid: r.out for r in done} == single
    eng.sched.assert_invariants()
    assert eng.sched.alloc.n_free == 9  # every page reclaimed


def test_paged_eos_token_stops_stream(params):
    probe = Request(uid=0, tokens=_prompt(6), max_new_tokens=6)
    _engine(params, max_batch=1, page_size=8, n_pages=16).run([probe])
    eos = probe.out[2]
    r = Request(uid=1, tokens=_prompt(6), max_new_tokens=50, eos_token=eos)
    _engine(params, max_batch=1, page_size=8, n_pages=16).run([r])
    expect = probe.out[:probe.out.index(eos) + 1]  # first occurrence stops
    assert r.out == expect and r.done


def test_paged_engine_pallas_matches_oracle(params):
    from repro.exec import PallasBackend
    spec = [(0, _prompt(6), 5), (1, _prompt(9, seed=2), 5)]
    outs = {}
    for be in ("oracle", PallasBackend(interpret=True)):
        eng = _engine(params, max_batch=2, page_size=8, n_pages=16,
                      backend=be)
        done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                        for u, t, n in spec])
        outs[str(be)] = {r.uid: r.out for r in done}
    vals = list(outs.values())
    assert vals[0] == vals[1]


# ---------------------------------------------------------------------------
# Chunked prefill: bit-parity with the token-by-token scan
# ---------------------------------------------------------------------------

def _scan_vs_chunk(cfg, params, L, chunks, backend, page_size=4, n_pages=16):
    """Prefill ``L`` prompt tokens token-by-token and as ``chunks``;
    return (states bit-equal, greedy next tokens equal)."""
    toks = ((np.arange(L) * 7 + 3) % cfg.vocab).astype(np.int32)
    n_slot_pages = -(-(L + 1) // page_size)
    table = jnp.asarray(np.arange(1, n_slot_pages + 1)[None])

    st_a = init_paged_decode_state(cfg, 1, page_size=page_size,
                                   n_pages=n_pages)
    for t in range(L):
        lg_a, st_a = decode_step_paged(
            params, cfg, st_a, jnp.asarray([[toks[t]]]),
            jnp.asarray([t]), table, backend=backend)

    st_b = init_paged_decode_state(cfg, 1, page_size=page_size,
                                   n_pages=n_pages)
    done = 0
    for c in chunks:
        lg_b, st_b = forward_paged_chunk(
            params, cfg, st_b, jnp.asarray(toks[done:done + c][None]),
            jnp.asarray([done]), table, backend=backend)
        done += c
    assert done == L

    bit_equal = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(st_a), jax.tree.leaves(st_b)))
    return bit_equal, (int(jnp.argmax(lg_a[0, -1]))
                       == int(jnp.argmax(lg_b[0, -1])))


@pytest.mark.parametrize("L,chunks", [
    (13, [8, 4, 1]),     # not a multiple of chunk (8) or page_size (4)
    (7, [4, 2, 1]),      # not a multiple of page_size
    (8, [8]),            # single full chunk
    (5, [1, 1, 1, 1, 1]),  # chunk=1 degenerates to the old per-token path
])
def test_chunked_prefill_bit_identical_to_scan(params, L, chunks):
    """The tentpole acceptance bar: a chunked prefill leaves EXACTLY the
    cache (INT8 codes via the same per-token bump-rescale, running
    exponents) and greedy next token that L single-token steps leave."""
    bit_equal, greedy_same = _scan_vs_chunk(CFG, params, L, chunks, "oracle")
    assert bit_equal and greedy_same


def test_chunked_prefill_bit_identical_pallas(params):
    from repro.exec import PallasBackend
    bit_equal, greedy_same = _scan_vs_chunk(
        CFG, params, 13, [8, 4, 1], PallasBackend(interpret=True))
    assert bit_equal and greedy_same


def test_chunked_prefill_recurrent_arch_bit_identical():
    """Mixed attn/rwkv/rglru stack: the chunked path must force the exact
    sequential recurrences (rwkv impl="scan", rglru exact_scan) so the
    carried states match the per-token scan bit-for-bit even when the
    config asks for the chunk-parallel WKV."""
    cfg = ModelConfig(name="m", family="dense", n_layers=3, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                      dtype="float32",
                      block_pattern=("attn", "rwkv", "rglru"),
                      d_rnn=32, wkv_impl="chunked", wkv_chunk=4)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    bit_equal, greedy_same = _scan_vs_chunk(cfg, p, 13, [8, 4, 1], "oracle")
    assert bit_equal and greedy_same


def test_engine_chunk1_matches_chunked(params):
    """Whole-engine degeneracy: prefill_chunk=1 (the old token-by-token
    behavior) and a chunked engine produce identical streams."""
    spec = [(i, _prompt(5 + 3 * i, seed=i), 6) for i in range(3)]
    outs = {}
    for chunk in (1, 8):
        eng = _engine(params, max_batch=3, page_size=4, n_pages=32,
                      prefill_chunk=chunk)
        done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                        for u, t, n in spec])
        outs[chunk] = {r.uid: r.out for r in done}
        eng.sched.assert_invariants()
    assert outs[1] == outs[8]


def test_prefill_pauses_at_chunk_boundary_and_resumes(params):
    """A prefill that cannot grow its next chunk's pages (older slot holds
    the pool) pauses WITHOUT preemption — it keeps its slot, pages and
    ``prefilled_len`` — and resumes from the same chunk boundary once the
    older request drains.  Outputs match a roomy engine exactly."""
    a = Request(uid=0, tokens=_prompt(8, seed=1), max_new_tokens=4)
    b = Request(uid=1, tokens=_prompt(12, seed=2), max_new_tokens=4)
    roomy = _single_stream(params, [(0, a.tokens, 4), (1, b.tokens, 4)])

    # decode_horizon=1: the pause needs the older slot to hold the pool
    # across >= 2 heartbeats; a fused horizon drains it in one macro-step
    # (the horizon-shrink path has its own test below).
    eng = _engine(params, max_batch=2, page_size=4, n_pages=5,
                  prefill_chunk=4, decode_horizon=1)
    eng.add_request(Request(uid=0, tokens=a.tokens, max_new_tokens=4))
    eng.add_request(Request(uid=1, tokens=b.tokens, max_new_tokens=4))
    done, paused, snaps = [], False, []
    for _ in range(64):
        done.extend(eng.step())
        eng.sched.assert_invariants()
        snap = (dict(eng._mid_prefill).keys(), eng.pos.copy())
        if snaps:
            prev_mid, prev_pos = snaps[-1]
            for s in eng._mid_prefill:
                if s in prev_mid and eng.pos[s] == prev_pos[s] > 0:
                    paused = True       # same boundary across two steps
        snaps.append(snap)
        if len(done) == 2:
            break
    assert len(done) == 2
    assert paused, "pool never forced a prefill pause"
    assert eng.sched.stats.preempted == 0  # paused, not evicted
    assert {r.uid: r.out for r in done} == roomy


def test_mid_prefill_preemption_replays_exactly(params):
    """Full preemption of a mid-prefill slot (decode eviction picks the
    latest-admitted victim) releases its pages; on re-admission it
    re-prefills from scratch and still matches the roomy engine."""
    spec = [(i, _prompt(10 + i, seed=i), 6) for i in range(4)]
    single = _single_stream(params, spec)
    # decode_horizon=1 keeps decode slow enough that prefill collides
    # with live decode pages (horizon preemption is covered below).
    eng = _engine(params, max_batch=4, page_size=4, n_pages=8,
                  prefill_chunk=4, prefill_token_budget=4,
                  decode_horizon=1)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert eng.sched.stats.preempted > 0, "pool was not small enough"
    assert {r.uid: r.out for r in done} == single
    eng.sched.assert_invariants()
    assert eng.sched.alloc.n_free == 7


def test_request_lifecycle_stamps(params):
    """Every request is stamped submit <= first admission <= first token
    read on the host; a preempted request keeps its first admission's
    stamp when it is admitted again."""
    spec = [(i, _prompt(10 + i, seed=i), 6) for i in range(4)]
    eng = _engine(params, max_batch=4, page_size=4, n_pages=8,
                  prefill_chunk=4, prefill_token_budget=4,
                  decode_horizon=1)
    first_admit = {}
    preempt = eng.sched.preempt

    def preempt_noting_admission(slot):
        r = eng.sched.slots[slot]
        first_admit.setdefault(r.uid, r.t_admit)
        return preempt(slot)

    eng.sched.preempt = preempt_noting_admission
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert len(done) == len(spec)
    assert first_admit, "pool was not small enough"
    for r in done:
        assert r.t_submit <= r.t_admit <= r.t_prefilled
    by_uid = {r.uid: r for r in done}
    for uid, t in first_admit.items():
        assert t is not None and by_uid[uid].t_admit == t


def test_first_token_stamped_in_the_heartbeat_of_its_last_chunk(params):
    """A request is stamped ``t_prefilled`` in the heartbeat whose prefill
    chunk finished its prompt, when its first token reached the host,
    and not before; a request done on that token is stamped too."""
    spec = [(i, _prompt(9 + 3 * i, seed=i), 1 + 4 * (i % 2))
            for i in range(4)]
    eng = _engine(params, max_batch=2, page_size=4, n_pages=16,
                  prefill_chunk=4, prefill_token_budget=4)
    reqs = [Request(uid=u, tokens=t, max_new_tokens=n) for u, t, n in spec]
    for r in reqs:
        eng.add_request(r)
    done = []
    while len(done) < len(reqs):
        before = {r.uid: r.t_prefilled for r in reqs}
        t0 = time.perf_counter()
        done.extend(eng.step())
        t1 = time.perf_counter()
        for r in reqs:
            assert (r.t_prefilled is None) == (not r.out)
            if r.out and before[r.uid] is None:   # first token this beat
                assert t0 <= r.t_prefilled <= t1
            elif r.out:
                assert r.t_prefilled == before[r.uid]
    assert all(r.t_admit <= r.t_prefilled for r in reqs)
    assert {r.uid for r in done if len(r.out) == 1} == {0, 2}


@pytest.fixture(scope="module")
def deployed_smoke():
    """The smoke tinyllama config at APSQ W8A8, calibrated and exported,
    so that every projection runs through ``execute_gemm``."""
    import dataclasses

    from repro.configs import get_smoke
    from repro.core import QuantConfig
    from repro.quant import calibrate_model, export_quantized
    cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"),
                              quant=QuantConfig.apsq(gs=2, n_p=4))
    p = init_lm(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    return cfg, export_quantized(calibrate_model(p, cfg, {"tokens": tok}))[0]


@pytest.mark.parametrize("program,want", [
    ("decode", {"decode", "sample", "lm_head", "attn", "mlp", "kv_update",
                "kv_attention", "apsq_gemm", "quantize", "rescale"}),
    ("prefill", {"prefill", "lm_head", "attn", "mlp", "kv_update",
                 "kv_attention", "apsq_gemm", "quantize", "rescale"}),
])
def test_compiled_programs_carry_named_scopes(deployed_smoke, program, want):
    """The engine's compiled decode and prefill programs name their ops'
    layers in the ``op_name`` metadata the profiler reads."""
    import re
    cfg, dep = deployed_smoke
    eng = PagedServingEngine(dep, cfg, backend="oracle", max_batch=2,
                             page_size=4, n_pages=16, prefill_chunk=4)
    B = eng.max_batch
    zi = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng.sched.table)
    if program == "decode":
        lowered = eng._decode.lower(
            2, eng.params, eng.state, jnp.zeros((B, 1), jnp.int32), zi,
            table, jnp.ones((B,), bool), zi + 2, zi + 2, zi - 1, eng.rng)
    else:
        lowered = eng._prefill_chunk.lower(
            eng.params, eng.state, jnp.zeros((1, 4), jnp.int32),
            jnp.int32(0), jnp.int32(0), table[:1])
    text = lowered.compile().as_text()
    names = {n for path in re.findall(r'op_name="([^"]*)"', text)
             for n in path.split("/")}
    assert want <= names, want - names
    other = "prefill" if program == "decode" else "decode"
    assert other not in names


def test_local_window_arch_rejected():
    cfg = ModelConfig(name="lw", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      dtype="float32", block_pattern=("local", "attn"),
                      local_window=8)
    p = init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError):
        PagedServingEngine(p, cfg, max_batch=1)


# ---------------------------------------------------------------------------
# Fused decode horizon (PR 10): H fused steps == H single steps, bit-exact
# ---------------------------------------------------------------------------

_HORIZON_CFGS = {
    "dense": CFG,
    "moe": ModelConfig(name="hm", family="dense", n_layers=2, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=96,
                       dtype="float32", mlp="moe", n_experts=4, top_k=2),
    "recurrent": ModelConfig(name="hr", family="dense", n_layers=3,
                             d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                             vocab=96, dtype="float32",
                             block_pattern=("attn", "rwkv", "rglru"),
                             d_rnn=32),
}


def _ref_single_steps(cfg, p, state, tokens, pos, table, h, *, active,
                      budget, remaining, eos, rng, backend):
    """H UNFUSED ``decode_step_paged`` calls with the engine's host-side
    masking — the de-fused reference ``decode_horizon_paged`` must match
    bit-for-bit (tokens, emitted mask, positions, every state leaf)."""
    from repro.models.model import paged_state_axes
    axes = paged_state_axes(state, cfg.scan_layers)
    act, bud, rem = map(jnp.asarray, (active, budget, remaining))
    toks, ons = [], []
    for _ in range(h):
        on = act & (bud > 0)
        tbl = jnp.where(on[:, None], table, NULL_PAGE)
        lg, st2 = decode_step_paged(p, cfg, state, tokens,
                                    jnp.where(on, pos, 0), tbl,
                                    backend=backend)

        def keep(old, new, ax):
            if ax == -1:
                return new
            m = on.reshape((1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
            return jnp.where(m, new, old)

        state = jax.tree.map(keep, state, st2, axes)
        rng, sub = jax.random.split(rng)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        rem = jnp.where(on, rem - 1, rem)
        fin = on & ((nxt == eos) | (rem <= 0))
        tokens = jnp.where(on, jnp.where(fin, 0, nxt), tokens[:, 0])[:, None]
        pos = pos + on.astype(pos.dtype)
        act = act & ~fin
        bud = bud - on.astype(bud.dtype)
        toks.append(nxt)
        ons.append(on)
    return (jnp.stack(toks, 1), jnp.stack(ons, 1), state, pos, rng)


def _horizon_case(cfg, backend, *, h=4, eos=(-1, -1, -1), remaining=(9, 9, 9),
                  first=7):
    """Fused vs unfused horizon on a 3-slot batch (slot 2 rides inert);
    ``first`` is the token slot 0 is fed."""
    from repro.models.model import decode_horizon_paged
    p = init_lm(jax.random.PRNGKey(1), cfg)
    B, P = 3, 4
    state = init_paged_decode_state(cfg, B, page_size=P, n_pages=16)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [NULL_PAGE] * 3], jnp.int32)
    pos = jnp.asarray([0, 2, 0], jnp.int32)
    tokens = jnp.asarray([[first], [11], [0]], jnp.int32)
    kw = dict(active=jnp.asarray([True, True, False]),
              budget=jnp.asarray([h, h, 0], jnp.int32),
              remaining=jnp.asarray(remaining, jnp.int32),
              eos=jnp.asarray(eos, jnp.int32), rng=jax.random.PRNGKey(9))
    fused = decode_horizon_paged(p, cfg, state, tokens, pos, table,
                                 horizon=h, backend=backend, **kw)
    ref = _ref_single_steps(cfg, p, state, tokens, pos, table, h,
                            backend=backend, **kw)
    return fused, ref


def _assert_bit_identical(fused, ref):
    f_tok, f_on, f_st, f_pos, f_key = fused
    r_tok, r_on, r_st, r_pos, r_key = ref
    assert jnp.array_equal(f_tok, r_tok), (f_tok, r_tok)
    assert jnp.array_equal(f_on, r_on), (f_on, r_on)
    assert jnp.array_equal(f_pos, r_pos)
    assert jnp.array_equal(f_key, r_key)
    for fl, rl in zip(jax.tree.leaves(f_st), jax.tree.leaves(r_st)):
        assert fl.dtype == rl.dtype and jnp.array_equal(fl, rl)


@pytest.mark.parametrize("arch", sorted(_HORIZON_CFGS))
def test_horizon_fused_bit_identical_oracle(arch):
    """Tokens, emitted masks, positions AND every cache/recurrent state
    leaf (codes, exponents, rnn carries) match H single steps exactly."""
    fused, ref = _horizon_case(_HORIZON_CFGS[arch], "oracle")
    _assert_bit_identical(fused, ref)


@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_horizon_fused_bit_identical_pallas(arch):
    from repro.exec import PallasBackend
    be = PallasBackend(interpret=True)
    fused, ref = _horizon_case(_HORIZON_CFGS[arch], be)
    _assert_bit_identical(fused, ref)


def test_horizon_mid_eos_and_exhaustion_bit_identical():
    """A slot hitting EOS (slot 0) or its last token (slot 1) mid-horizon
    stops emitting, freezes its position, and writes only to the null
    page thereafter — bit-identical to the masked single-step path."""
    cfg = CFG
    # First pass to discover what slot 0 emits at step 1; use it as EOS.
    # Slot 0 is fed a token whose step-0 and step-1 outputs differ, so
    # the EOS first appears at step 1.
    (tok, _, _, _, _), _ = _horizon_case(cfg, "oracle", first=3)
    eos0 = int(tok[0, 1])
    assert int(tok[0, 0]) != eos0, tok
    fused, ref = _horizon_case(cfg, "oracle", eos=(eos0, -1, -1),
                               remaining=(9, 2, 9), first=3)
    _assert_bit_identical(fused, ref)
    f_on = np.asarray(fused[1])
    assert f_on[0].tolist() == [True, True, False, False]   # stopped at EOS
    assert f_on[1].tolist() == [True, True, False, False]   # out of tokens
    f_pos = np.asarray(fused[3])
    assert f_pos.tolist() == [2, 4, 0]


@pytest.mark.parametrize("h", [1, 2, 8])
def test_engine_horizon_matches_single_stream(params, h):
    """Whole-engine degeneracy sweep: any decode_horizon produces the
    same streams as the single-stream engine (h=1 IS the old path)."""
    spec = [(i, _prompt(4 + 2 * i, seed=i), 5 + i) for i in range(4)]
    single = _single_stream(params, spec)
    eng = _engine(params, max_batch=3, page_size=4, n_pages=32,
                  decode_horizon=h)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert {r.uid: r.out for r in done} == single
    eng.sched.assert_invariants()
    if h == 8:
        assert max(eng.horizon_hist) > 1      # fusion actually engaged


def test_engine_horizon_shrinks_under_near_dry_pool(params):
    """A tight pool shrinks macro-step budgets (grow_span never evicts)
    instead of preempting: outputs still match the roomy engine and some
    macro-steps run with fewer than decode_horizon fused steps."""
    spec = [(i, _prompt(6, seed=i), 10) for i in range(2)]
    single = _single_stream(params, spec)
    eng = _engine(params, max_batch=2, page_size=4, n_pages=7,
                  prefill_chunk=4, decode_horizon=8)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert {r.uid: r.out for r in done} == single
    eng.sched.assert_invariants()
    assert any(k < 8 for k in eng.horizon_hist), eng.horizon_hist


def test_engine_horizon_preemption_between_macro_steps(params):
    """A pool too small for both long decodes preempts the latest slot
    between macro-steps (never mid-scan); the replayed request still
    matches the single-stream outputs and no page leaks."""
    spec = [(0, _prompt(6, seed=3), 24), (1, _prompt(6, seed=4), 24)]
    single = _single_stream(params, spec)
    eng = _engine(params, max_batch=2, page_size=4, n_pages=9,
                  prefill_chunk=4, decode_horizon=8)
    done = []
    while eng.sched.waiting or any(s is not None for s in eng.sched.slots) \
            or not done:
        if not done and not eng.sched.waiting:
            for u, t, n in spec:
                eng.add_request(Request(uid=u, tokens=t, max_new_tokens=n))
        done.extend(eng.step())
        eng.sched.assert_invariants()          # after every macro-step
    assert eng.sched.stats.preempted > 0, "pool was not small enough"
    assert {r.uid: r.out for r in done} == single
    assert eng.sched.alloc.n_free == 8


def test_horizon_one_bit_identical_to_fused_path(params):
    """decode_horizon=1 and the pre-fusion single-step engine semantics
    coincide: dispatch counters show one launch per token."""
    spec = [(0, _prompt(5), 6)]
    eng = _engine(params, max_batch=1, page_size=4, n_pages=16,
                  decode_horizon=1)
    done = eng.run([Request(uid=u, tokens=t, max_new_tokens=n)
                    for u, t, n in spec])
    assert len(done[0].out) == 6
    # 1 token from prefill logits + 5 decode tokens, one launch each.
    assert eng.decode_dispatches == eng.decode_device_steps == 5
    assert set(eng.horizon_hist) == {1}


@pytest.mark.parametrize("h,launched,live", [
    # 7 decode steps, 2 slots, 2 tiles of 320 over 40 pages of 16: slot 0
    # attends over 317..323 positions (one tile up to 320, then two);
    # the empty slot over one position.
    (1, 7 * 2 * 2, 4 * 1 + 3 * 2 + 7),
    # One fused dispatch of 8 steps: slot 0 is done after its 7th.
    (8, 8 * 2 * 2, 4 * 1 + 3 * 2 + 1 + 8),
])
def test_kv_tile_counters(params, h, launched, live):
    """``kv_tiles_launched`` / ``kv_tiles_live`` replay the attention
    grid's KV tiles per slot and decode step, and the kernel's skip of
    those past each slot's length; ``reset_counters`` zeroes them."""
    eng = _engine(params, max_batch=2, page_size=16, n_pages=48,
                  max_pages_per_slot=40, prefill_chunk=16,
                  decode_horizon=h)
    assert eng._kv_tile == 320
    done = eng.run([Request(uid=0, tokens=_prompt(316), max_new_tokens=8)])
    assert len(done[0].out) == 8 and eng.decode_device_steps == 8 - (h == 1)
    assert (eng.kv_tiles_launched, eng.kv_tiles_live) == (launched, live)
    assert eng.kv_tiles_live <= eng.kv_tiles_launched
    eng.reset_counters()
    assert eng.kv_tiles_launched == eng.kv_tiles_live == 0
