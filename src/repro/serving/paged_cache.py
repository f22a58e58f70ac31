"""Paged INT8 KV cache: fixed-size PO2-scaled pages behind a page table.

The cache for one attention layer is a *pool* of fixed-size pages,

    k_pages / v_pages : int8  [n_pages, page_size, Hkv, hd]
    k_exp  / v_exp    : int32 [max_slots, Hkv]

shared by every request slot; a host-side page table (``[max_slots,
pages_per_slot]`` physical page ids, see ``repro.serving.scheduler``) maps
each slot's logical positions onto pool pages.  Page 0 is the reserved
*null page*: unallocated table entries point at it, writes to it are junk
and reads of it are always masked off by the valid length.

Scales are powers of two per (slot, kv-head) — the paper's RAE shifter
argument (§II-B) applied to the cache: dequantization is a shift, and
growing the scale re-quantizes existing codes with an integer
round-half-up right shift (``_shift_codes``), never a float pass.  The
running exponent only ever grows, and it depends only on the slot's own
tokens, so a request's decode is bit-identical regardless of which other
requests share the pool — the property the continuous-batching parity
tests pin down.

The read path dispatches through the ``repro.exec`` backend registry
(``execute_kv_attention``): the gathered page view is exactly the dense
[B, S, Hkv, hd] layout the ``kernels/int8_kv_attention`` flash-decode
kernel consumes, with ``block_s = page_size``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NULL_PAGE = 0
# Fresh-slot exponent: 2^-24 scale.  Any real activation bumps it; codes
# quantized at it are zero for all practical magnitudes.
EXP_FLOOR = -24


def page_span(start: int, end: int, page_size: int) -> range:
    """Page-aligned start positions of every page holding [start, end).

    The host-side page walk shared by prefill's chunk growth and the
    decode-horizon reservation: ``range(align_down(start), end,
    page_size)`` — empty when ``end <= start``."""
    return range(start - start % page_size, end, page_size)


def po2_exponent(x: jax.Array) -> jax.Array:
    """Smallest PO2 exponent whose 127-code range covers ``x``.

    x: [B, S, Hkv, hd] -> int32 [B, Hkv] (reduced over positions + dims).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 3))
    return jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / 127.0)).astype(
        jnp.int32)


def quantize_at(x: jax.Array, exp: jax.Array) -> jax.Array:
    """Float [B, S, Hkv, hd] -> int8 codes at the PO2 scale 2^exp[B, Hkv]."""
    scale = jnp.exp2(exp.astype(jnp.float32))[:, None, :, None]
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -127, 127).astype(jnp.int8)


def _shift_codes(codes: jax.Array, shift: jax.Array) -> jax.Array:
    """Re-quantize int8 codes to a coarser PO2 scale: round-half-up >> shift.

    codes: [B, n_pages, P, Hkv, hd] int8; shift: [B, Hkv] int32 >= 0.
    Matches the RAE's shift-with-rounding datapath — no float involved.
    """
    sh = shift[:, None, None, :, None]
    c = codes.astype(jnp.int32)
    half = jnp.where(sh > 0, 1 << jnp.maximum(sh - 1, 0), 0)
    return jnp.clip((c + half) >> sh, -127, 127).astype(jnp.int8)


def _bump_token(gathered: jax.Array, exp: jax.Array, x_new: jax.Array,
                pos: jax.Array):
    """One token of the running-exponent recurrence on a gathered view.

    gathered: [B, n_max, P, Hkv, hd] int8 (a slot's pages, gathered);
    exp: [B, Hkv] int32; x_new: [B, 1, Hkv, hd] float; pos: [B] int32.
    Bumps the exponent to cover the new token, re-quantizes existing codes
    with the integer round-half-up shift, writes the new token's codes.
    Both the per-token scan and the chunked writer are iterations of this
    exact step, which is what makes them bit-identical.
    """
    page_size = gathered.shape[2]
    b_idx = jnp.arange(x_new.shape[0])
    new_exp = jnp.maximum(exp, po2_exponent(x_new))
    gathered = _shift_codes(gathered, new_exp - exp)
    codes = quantize_at(x_new, new_exp)            # [B, 1, Hkv, hd]
    gathered = gathered.at[b_idx, pos // page_size,
                           pos % page_size].set(codes[:, 0])
    return gathered, new_exp


@jax.named_scope("kv_update")
def _update_pool(pages: jax.Array, exp: jax.Array, x_new: jax.Array,
                 pos: jax.Array, page_table: jax.Array):
    """Write one token per slot into the paged pool.

    pages: [n_pages, P, Hkv, hd] int8; exp: [B, Hkv] int32 (running);
    x_new: [B, 1, Hkv, hd] float; pos: [B] int32; page_table: [B, n_max].
    Returns (pages', exp', gathered [B, n_max, P, Hkv, hd]) — the gathered
    view already contains the new token, so the attention read reuses it.
    """
    gathered, new_exp = _bump_token(pages[page_table], exp, x_new, pos)
    pages = pages.at[page_table].set(gathered)
    return pages, new_exp, gathered


@jax.named_scope("kv_update")
def _update_pool_chunk(pages: jax.Array, exp: jax.Array, x_new: jax.Array,
                       pos: jax.Array, page_table: jax.Array):
    """Write a [chunk] of tokens per slot with the per-token bump sequence.

    x_new: [B, C, Hkv, hd] float; pos: [B] int32 (position of the chunk's
    FIRST token).  Round-half-up shifts do not compose (shifting by d1
    then d2 is not shifting by d1+d2), so the chunk writer must replay the
    exact per-token ``_bump_token`` recurrence the decode scan runs — the
    pool is gathered once, iterated in registers, scattered once.

    Returns (pages', exp', gathered, exps_seq [C, B, Hkv]) where
    ``exps_seq[t]`` is the running exponent after the chunk's token ``t``
    — the attention path uses it to detect mid-chunk bumps.
    """
    gathered = pages[page_table]

    def step(carry, xs):
        g, e = carry
        xt, t = xs
        g, e = _bump_token(g, e, xt[:, None], pos + t)
        return (g, e), e

    xs = (jnp.moveaxis(x_new, 1, 0), jnp.arange(x_new.shape[1]))
    (gathered, new_exp), exps_seq = jax.lax.scan(step, (gathered, exp), xs)
    pages = pages.at[page_table].set(gathered)
    return pages, new_exp, gathered, exps_seq


def paged_update_and_attend(cache: dict, q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, pos: jax.Array,
                            page_table: jax.Array, *, backend=None):
    """One decode step against the paged INT8 cache: write, then attend.

    cache: {"k_pages", "v_pages" [n_pages, P, Hkv, hd] int8;
            "k_exp", "v_exp" [B, Hkv] int32}
    q: [B, Hq, hd] float; k_new/v_new: [B, 1, Hkv, hd] (roped already);
    pos: [B] int32 (position being written); page_table: [B, n_max].

    Returns (out [B, Hq, hd], new_cache).  The attention itself runs
    through ``repro.exec.execute_kv_attention`` with ``block_s`` = the
    page size, so the serving read path is the registered op family
    (the jnp oracle or the Pallas flash-decode kernel, per ``backend``).
    """
    from repro.exec import execute_kv_attention
    pos = jnp.asarray(pos, jnp.int32)
    k_pages, k_exp, gk = _update_pool(cache["k_pages"], cache["k_exp"],
                                      k_new, pos, page_table)
    v_pages, v_exp, gv = _update_pool(cache["v_pages"], cache["v_exp"],
                                      v_new, pos, page_table)
    b, n_max, page_size = gk.shape[:3]
    k_seq = gk.reshape(b, n_max * page_size, *gk.shape[3:])
    v_seq = gv.reshape(b, n_max * page_size, *gv.shape[3:])
    out = execute_kv_attention(q, k_seq, v_seq, k_exp, v_exp, pos + 1,
                               block_s=page_size, backend=backend)
    return out, {"k_pages": k_pages, "v_pages": v_pages,
                 "k_exp": k_exp, "v_exp": v_exp}


def paged_prefill_chunk_update_and_attend(cache: dict, q: jax.Array,
                                          k_new: jax.Array, v_new: jax.Array,
                                          pos: jax.Array,
                                          page_table: jax.Array, *,
                                          backend=None):
    """One prefill chunk against the paged INT8 cache: write C tokens,
    attend C causal query rows — bit-identical to C iterations of
    ``paged_update_and_attend``.

    q: [B, C, Hq, hd] float; k_new/v_new: [B, C, Hkv, hd] (roped);
    pos: [B] int32 — position of the chunk's FIRST token.

    The cache write replays the per-token bump recurrence exactly
    (``_update_pool_chunk``), so pools and exponents always match the
    scan.  The attention read has two regimes:

    * **stable** (the overwhelmingly common case): the running exponents
      after the chunk's first token already cover the whole chunk — every
      query row then sees the same codes the scan saw, and one chunked
      ``execute_kv_attention`` call with the in-chunk causal mask is
      bit-identical.
    * **mid-chunk bump**: a later token grew an exponent, so the scan's
      earlier rows attended over *finer* codes than the final view holds
      (the round-half-up rescale is lossy).  Fall back to replaying the
      per-row snapshots from the pre-chunk pools — still one fused device
      computation, selected by ``lax.cond`` so the fast path pays nothing.
    """
    from repro.exec import execute_kv_attention
    pos = jnp.asarray(pos, jnp.int32)
    chunk = q.shape[1]
    page_size = cache["k_pages"].shape[1]
    gk0 = cache["k_pages"][page_table]
    gv0 = cache["v_pages"][page_table]
    k_pages, k_exp, gk, k_exps = _update_pool_chunk(
        cache["k_pages"], cache["k_exp"], k_new, pos, page_table)
    v_pages, v_exp, gv, v_exps = _update_pool_chunk(
        cache["v_pages"], cache["v_exp"], v_new, pos, page_table)
    b, n_max = gk.shape[:2]
    seq = n_max * page_size

    def attend_stable(_):
        k_seq = gk.reshape(b, seq, *gk.shape[3:])
        v_seq = gv.reshape(b, seq, *gv.shape[3:])
        return execute_kv_attention(q, k_seq, v_seq, k_exp, v_exp,
                                    pos + chunk, block_s=page_size,
                                    backend=backend)

    def attend_replay(_):
        def step(carry, xs):
            cgk, cke, cgv, cve = carry
            qt, kt, vt, t = xs
            cgk, cke = _bump_token(cgk, cke, kt[:, None], pos + t)
            cgv, cve = _bump_token(cgv, cve, vt[:, None], pos + t)
            out_t = execute_kv_attention(
                qt, cgk.reshape(b, seq, *cgk.shape[3:]),
                cgv.reshape(b, seq, *cgv.shape[3:]), cke, cve,
                pos + t + 1, block_s=page_size, backend=backend)
            return (cgk, cke, cgv, cve), out_t

        xs = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k_new, 1, 0),
              jnp.moveaxis(v_new, 1, 0), jnp.arange(chunk))
        carry = (gk0, cache["k_exp"], gv0, cache["v_exp"])
        _, outs = jax.lax.scan(step, carry, xs)
        return jnp.moveaxis(outs, 0, 1)            # [B, C, Hq, hd]

    stable = (jnp.all(k_exps[0] == k_exp) & jnp.all(v_exps[0] == v_exp))
    out = jax.lax.cond(stable, attend_stable, attend_replay, None)
    return out, {"k_pages": k_pages, "v_pages": v_pages,
                 "k_exp": k_exp, "v_exp": v_exp}


def paged_cache_bytes(cfg, *, n_pages: int, page_size: int,
                      max_batch: int, cache_len: int) -> dict:
    """Device bytes of the paged INT8 pools vs the dense f32/bf16 caches.

    Counts every full-attention layer ("attn" kind) of ``cfg``; the dense
    baseline is what ``init_decode_state`` allocates per slot.
    """
    n_attn = sum(1 for k in cfg.block_pattern if k == "attn")
    n_attn *= cfg.n_units
    n_attn += sum(1 for k in cfg.block_pattern[:cfg.n_rem] if k == "attn")
    per_tok = cfg.n_kv_heads * cfg.hd * 2          # k and v
    el = jnp.dtype(cfg.dtype).itemsize
    return {
        "int8_paged": n_attn * (n_pages * page_size * per_tok
                                + max_batch * cfg.n_kv_heads * 2 * 4),
        "dense_f32": n_attn * max_batch * cache_len * per_tok * 4,
        "dense_native": n_attn * max_batch * cache_len * per_tok * el,
        "n_attn_layers": n_attn,
    }
