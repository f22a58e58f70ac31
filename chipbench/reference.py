"""Plain reference of the served model: the same equations, written out.

It imports nothing of the program.  It takes the configuration's sizes,
the static exponents of each linear, and draws the weights itself from
the seed through ``chipbench.weights`` (the benchmark's own generator),
one layer at a time, so it fits beside nothing else on the chip.  What a
layer holds is its family's (``chipbench/families``); the pieces here are
shared by every family.

Semantics, as the configuration states them:

* every projection is W8A8 with APSQ partial sums (Algorithm 1): the
  input is quantized to INT8 at 2^ax (round half to even, clip), the
  K axis is cut into n_p tiles whose INT32 products are stored as INT8
  codes at 2^e_i (shift with round half up, clip), group starts add the
  previous group's stored codes back (APSQ), the rest are plain PSQ, and
  the last tile is dequantized and scaled by 2^(ax + aw).
* rotary embedding on the leading ``rope_fraction`` of each head
  (interleaved pairs), then an INT8 KV cache: per (sequence, kv head) a
  running power-of-two exponent that only grows, the smallest covering
  each new token (amax / 127); stored codes are re-quantized to a grown
  exponent by a shift with round half up.  Query t attends over the
  codes as they stand after token t is written, in float32.
* the final RMSNorm and the untied LM head, a float32 GEMM.

``bits`` lowers the integer widths (the control): activation codes,
partial-sum codes and KV codes at ``bits`` instead of 8, over the same
ranges (exponents raised by 8 - bits).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXP_FLOOR = -24
NEG_INF = -1e30


def _rshift_round(v, s: int):
    """Integer shift right by a static ``s`` >= 0, rounding half up."""
    if s <= 0:
        return v
    return jnp.right_shift(v + (1 << (s - 1)), s)


def _algorithm1(tiles: list, exps: list, gs: int, bits: int):
    """APSQ over INT32 partial-sum tiles (list of [M, N]); returns the
    dequantized INT32 output in product-scale units."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    n = len(tiles)

    def q(v, e):
        return jnp.clip(_rshift_round(v, e), lo, hi)

    def dq(c, e):
        return jnp.left_shift(c, e)

    stored = [None] * n
    for i in range(0, n, gs):
        acc = tiles[i]
        for j in range(max(0, i - gs), i):
            acc = acc + dq(stored[j], exps[j])
        stored[i] = q(acc, exps[i])
        if i == n - 1:
            return dq(stored[i], exps[i])
        for j in range(i + 1, min(i + gs, n)):
            if j < n - 1:
                stored[j] = q(tiles[j], exps[j])
            else:
                acc = tiles[j]
                for k in range(i, n - 1):
                    acc = acc + dq(stored[k], exps[k])
                return dq(q(acc, exps[j]), exps[j])
    raise AssertionError("unreachable")


def qgemm(x, codes, m: dict, bits: int = 8):
    """Deployed linear: x [T, K] bf16 @ INT8 codes [K, N] -> bf16."""
    drop = 8 - bits
    ax = m["ax"] + drop
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    xc = jnp.clip(jnp.round(x.astype(jnp.float32) * 2.0 ** (-ax)), lo, hi)
    xc = xc.astype(jnp.int8)
    k = codes.shape[0]
    n_p = len(m["ps"])
    kt = -(-k // n_p)
    pad = kt * n_p - k
    if pad:
        xc = jnp.pad(xc, ((0, 0), (0, pad)))
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    tiles = [jax.lax.dot_general(
        xc[:, i * kt:(i + 1) * kt], codes[i * kt:(i + 1) * kt],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        for i in range(n_p)]
    gs = n_p if m["mode"] == "psq" else m["gs"]
    y = _algorithm1(tiles, [e + drop for e in m["ps"]], gs, bits)
    return (y.astype(jnp.float32) * 2.0 ** (ax + m["aw"])).astype(x.dtype)


def rmsnorm(x, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype)                     # gains are ones


def rope(x, pos, fraction: float, theta: float):
    """x [T, H, hd]; rotary on the first ``fraction`` of dims, pairs
    (0, 1), (2, 3), ... rotated by pos * theta^(-2i / rot)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1 = x[..., 0:rot:2].astype(jnp.float32)
    x2 = x[..., 1:rot:2].astype(jnp.float32)
    o = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    o = o.reshape(x.shape[:-1] + (rot,)).astype(x.dtype)
    return jnp.concatenate([o, x[..., rot:]], axis=-1)


def kv_attention(q, k, v, bits: int = 8):
    """Causal attention through the running-exponent INT8 KV cache.

    q [T, Hq, hd], k/v [T, Hkv, hd] (bf16, roped).  A scan over tokens:
    write token t (growing the exponent and shifting stored codes when
    it must), then attend query t over codes 0..t."""
    T, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qmax = 2 ** (bits - 1) - 1
    scale = 1.0 / math.sqrt(hd)

    def write(codes, e, x, t):
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)   # [Hkv]
        need = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / qmax))
        en = jnp.maximum(e, need.astype(jnp.int32))
        sh = en - e

        def shift(c):
            s = sh[None, :, None]
            c32 = c.astype(jnp.int32)
            half = jnp.where(s > 0, jnp.left_shift(1, jnp.maximum(s - 1, 0)),
                             0)
            return jnp.clip(jnp.right_shift(c32 + half, s), -qmax,
                            qmax).astype(jnp.int8)

        codes = jax.lax.cond(jnp.any(sh > 0), shift, lambda c: c, codes)
        new = jnp.clip(jnp.round(x.astype(jnp.float32)
                                 / jnp.exp2(en.astype(jnp.float32))[:, None]),
                       -qmax, qmax).astype(jnp.int8)
        return codes.at[t].set(new), en

    def step(carry, xs):
        kc, ke, vc, ve = carry
        qt, kt, vt, t = xs
        kc, ke = write(kc, ke, kt, t)
        vc, ve = write(vc, ve, vt, t)
        qf = qt.astype(jnp.float32).reshape(hkv, g, hd)
        s = jnp.einsum("hgd,shd->hgs", qf, kc.astype(jnp.float32),
                       precision=HI)
        s = s * (scale * jnp.exp2(ke.astype(jnp.float32)))[:, None, None]
        s = jnp.where(jnp.arange(T)[None, None, :] <= t, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        o = jnp.einsum("hgs,shd->hgd", p, vc.astype(jnp.float32),
                       precision=HI)
        o = o * jnp.exp2(ve.astype(jnp.float32))[:, None, None]
        o = o / jnp.sum(p, axis=-1)[..., None]
        return (kc, ke, vc, ve), o.reshape(hq, hd).astype(qt.dtype)

    z = jnp.zeros((T, hkv, hd), jnp.int8)
    e0 = jnp.full((hkv,), EXP_FLOOR, jnp.int32)
    _, out = jax.lax.scan(step, (z, e0, z, e0),
                          (q, k, v, jnp.arange(T)))
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, rows, head_w, *, eps):
    """Float32 logits of ``rows`` of the final hidden states."""
    h = rmsnorm(x[rows], eps)
    return jnp.dot(h.astype(jnp.float32), head_w.astype(jnp.float32),
                   precision=HI)


class Reference:
    """Logits of the plain model for given token sequences.

    ``layer(x, codes, *, dims, meta, bits)`` is the family's jitted
    layer (``dims`` and ``meta`` as sorted tuples).  ``dims``: n_units,
    eps and whatever the layer reads.  ``meta``: {linear name:
    exponents} from ``chipbench.weights.linear_meta``.  ``draw_unit(u)``
    gives unit u's INT8 codes, ``embed``/``head`` the float tables."""

    def __init__(self, layer, dims: dict, meta: dict, draw_unit, embed,
                 head, t_pad: int, bits: int = 8):
        self.layer = layer
        self.dims = tuple(sorted(dims.items()))
        self.meta = tuple(sorted((k, tuple(sorted(v.items())))
                                 for k, v in meta.items()))
        self.n_units = dims["n_units"]
        self.eps = dims["eps"]
        self.draw_unit = draw_unit
        self.embed, self.head = embed, head
        self.t_pad = t_pad
        self.bits = bits

    def logits(self, seqs: list, rows: list, bits: int | None = None):
        """For each token sequence, float32 logits [len(rows_i), V] at the
        given positions.  Layer by layer: each unit's codes are drawn once
        and applied to every sequence."""
        bits = self.bits if bits is None else bits
        xs = []
        for s in seqs:
            t = np.zeros(self.t_pad, np.int32)
            t[:len(s)] = s
            xs.append(jnp.take(self.embed, jnp.asarray(t), axis=0))
        for u in range(self.n_units):
            codes = self.draw_unit(u)
            xs = [self.layer(x, codes, dims=self.dims, meta=self.meta,
                             bits=bits)
                  for x in xs]
            del codes
        out = []
        for x, r in zip(xs, rows):
            rp = np.zeros(self.t_pad, np.int32)   # fixed shape: one compile
            rp[:len(r)] = r
            lg = head_logits(x, jnp.asarray(rp), self.head, eps=self.eps)
            out.append(np.asarray(lg[:len(r)]))
        return out


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the served token's reference logit lies
    below the reference's best."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - got
