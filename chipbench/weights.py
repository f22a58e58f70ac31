"""The deployed INT8 model made from the seed, on the device, in one call.

The served tree is what ``repro.quant.export`` hands the engine: every
quantized linear becomes a ``DeployedQuantState`` (INT8 weight codes,
activation / weight / partial-sum shift exponents; stacked over experts
where the family has them); the embedding table, the norms and the
untied LM head stay in the model's float dtype.  Its structure and
shapes are the family's ``deployed_shapes(cfg)`` (``chipbench/families``:
``jax.eval_shape`` of the program's own ``init_lm`` and export), so no
float model is ever held.

Every value is a hash (murmur3's finalizer) of its index within its
array, keyed per leaf from the 64-bit seed and, for scan-stacked units,
per unit: elementwise, so the whole tree is one jitted call that writes
only its outputs, and the reference draws any one unit alone and gets
the same values.  Weight codes are symmetric on [-15, 15]; the exponents
are set as a calibrated deployment's would be (the rule of
``repro.core.init_alpha_from``, 2 * mean|x| / sqrt(127), snapped down to
a power of two), from the activation magnitude each linear sees, so
that activations and partial sums stay in INT8 range.  What a linear's
input magnitude is, and how each float leaf is drawn, is the family's
word: its ``mean_abs_in(names)`` and ``float_init(names, shape)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Weight codes: one random byte per weight, arithmetic-shifted right by 3
# to [-16, 15], with -16 folded onto 0 so that the codes have zero mean
# (a mean would add the same term to every output column and, layer after
# layer, drive the residual stream along one direction).
CODE_SHIFT = 3
CODE_STD = math.sqrt(2 * sum(k * k for k in range(1, 16)) / 32.0)
# Mean |x| of a unit-scale activation (mean |N(0,1)|), such as an
# RMS-normed input.
MEAN_ABS_UNIT = math.sqrt(2.0 / math.pi)
QMAX = 127.0


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file's dict."""
    from repro.configs import get_config, get_smoke
    from repro.core import PsumQuantConfig, QuantConfig

    cfg = get_smoke(conf["arch"]) if conf.get("smoke") else get_config(
        conf["arch"])
    if conf.get("model_cfg"):                       # test-size overrides
        cfg = cfg.scaled(**conf["model_cfg"])
    q = conf["quant"]
    quant = QuantConfig(enabled=True, w_bits=q.get("bits", 8),
                        a_bits=q.get("bits", 8),
                        psum=PsumQuantConfig(q["mode"], gs=q["gs"],
                                             n_p=q["n_p"],
                                             bits=q.get("bits", 8)))
    return cfg.with_quant(quant).scaled(dtype=conf.get("dtype", cfg.dtype))


def _ax_exp(m: float) -> int:
    return math.floor(math.log2(2.0 * m / math.sqrt(QMAX)))


def linear_exponents(m: float, k: int, n_p: int):
    """(ax_exp, aw_exp, [psum exps]) of one linear with reduction dim K
    whose float input has mean |x| ``m``.

    aw: codes of std CODE_STD times 2^aw give the fan-in scale 1/sqrt(K).
    PSUM tile i holds the running sum of (i+1) K/n_p products; its scale
    is 2 * mean|running| / sqrt(127), in product-scale units."""
    ax = _ax_exp(m)
    aw = round(math.log2(1.0 / (CODE_STD * math.sqrt(k))))
    x_code_std = (m / MEAN_ABS_UNIT) * 2.0 ** (-ax)
    kt = -(-k // n_p)
    ps = []
    for i in range(n_p):
        mean_abs = MEAN_ABS_UNIT * x_code_std * CODE_STD * math.sqrt(
            kt * (i + 1))
        ps.append(max(0, math.floor(math.log2(2.0 * mean_abs
                                              / math.sqrt(QMAX)))))
    return ax, aw, ps


def _mix32(x):
    """murmur3's 32-bit finalizer: a bijective mix of uint32 values."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _hash(key, shape):
    """uint32 [*shape] from a uint32 key: the mix of each element's
    index within the array, xor the key.  Elementwise, so XLA writes it
    straight into the output with no temporaries."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) * \
            np.uint32(stride)
        stride *= shape[ax]
    return _mix32(idx ^ key)


def _unit_key(key, u):
    return _mix32(key ^ _mix32(jnp.asarray(u, jnp.uint32) + np.uint32(1)))


def _codes(key, shape):
    """int8 codes on [-15, 15], symmetric: the top byte of each hash,
    arithmetic-shifted right by 3, with -16 folded onto 0."""
    b = (_hash(key, shape) >> 24).astype(jnp.int32) - 128
    c = jnp.right_shift(b, CODE_SHIFT)
    return jnp.where(c == -16, 0, c).astype(jnp.int8)


def _uniform(key, shape, std, dtype):
    """Uniform values of the given std (zero mean)."""
    u = (_hash(key, shape) >> 8).astype(jnp.float32) * (2.0 ** -24)
    return ((u - 0.5) * (math.sqrt(12.0) * std)).astype(dtype)


def _names(path) -> list:
    return [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]


def _stacked(path) -> bool:
    """Leaves under ``units`` carry the scan's leading unit axis."""
    return bool(path) and getattr(path[0], "key", None) == "units"


def _float(key, shape, init, dtype):
    """A float leaf from its family's ``(mean, std)``: the constant mean
    where std is 0, else uniform values of that std about the mean."""
    mean, std = init
    if not std:
        return jnp.full(shape, mean, dtype)
    return (mean + _uniform(key, shape, std, jnp.float32)).astype(dtype)


def _per_unit(fn, key, n_units: int):
    """``fn(unit key)`` for every unit, stacked on a leading axis."""
    return jax.vmap(lambda u: fn(_unit_key(key, u)))(
        jnp.arange(n_units, dtype=jnp.uint32))


def _float_leaf(key, path, leaf, float_init):
    shape = leaf.shape[1:] if _stacked(path) else leaf.shape
    init = float_init(_names(path), shape)
    fn = lambda k: _float(k, shape, init, leaf.dtype)  # noqa: E731
    return _per_unit(fn, key, leaf.shape[0]) if _stacked(path) else fn(key)


def _linear(key, path, t, mean_abs: float):
    """A ``DeployedQuantState`` with codes drawn from ``key`` (its
    ``w_codes`` leaf's) and the exponents of an input of mean |x|
    ``mean_abs``.  Any leading axes (units, experts) share them."""
    from repro.core import DeployedQuantState

    shape = t.w_codes.shape
    if _stacked(path):
        codes = _per_unit(lambda k: _codes(k, shape[1:]), key, shape[0])
    else:
        codes = _codes(key, shape)
    ax, aw, ps = linear_exponents(mean_abs, shape[-2], t.psum_exps.shape[-2])
    ps_e = jnp.broadcast_to(jnp.asarray(ps, jnp.int32)[:, None],
                            t.psum_exps.shape[-2:])
    return DeployedQuantState(
        w_codes=codes, ax_exp=jnp.full(t.ax_exp.shape, ax, jnp.int32),
        aw_exp=jnp.full(t.aw_exp.shape, aw, jnp.int32),
        psum_exps=jnp.broadcast_to(ps_e, t.psum_exps.shape),
        spec=t.spec, name=t.name, out_dims=t.out_dims)


def leaf_paths(abstract):
    """[(path, leaf)] in flatten order: the leaf index is its key."""
    return jax.tree_util.tree_leaves_with_path(abstract)


def _leaf_index(abstract) -> dict:
    return {jax.tree_util.keystr(p): i
            for i, (p, _) in enumerate(leaf_paths(abstract))}


def _codes_path(path):
    return path + (jax.tree_util.GetAttrKey("w_codes"),)


def _build(key, abstract, mean_abs_in, float_init):
    from repro.core import DeployedQuantState

    index = _leaf_index(abstract)

    def walk(t, prefix):
        if isinstance(t, DeployedQuantState):
            i = index[jax.tree_util.keystr(_codes_path(prefix))]
            return _linear(key[i], prefix, t, mean_abs_in(_names(prefix)))
        if isinstance(t, dict):
            return {k: walk(v, prefix + (jax.tree_util.DictKey(k),))
                    for k, v in t.items()}
        i = index[jax.tree_util.keystr(prefix)]
        return _float_leaf(key[i], prefix, t, float_init)

    return walk(abstract, ())


def make_params(seed: int, abstract, mean_abs_in, float_init):
    """The whole deployed tree on the default device, in one jitted call.
    ``abstract``, ``mean_abs_in`` and ``float_init`` are the family's
    ``deployed_shapes(cfg)`` and rules."""
    return jax.jit(lambda k: _build(k, abstract, mean_abs_in, float_init))(
        seed_key(seed))


def unit_leaves(abstract) -> dict:
    """{dotted path below ``units``: (abstract, path)} of every linear
    (a whole ``DeployedQuantState``, e.g. ``0.mix.wq.qp``) and every
    float leaf (``0.ln1.scale``) of the scan units."""
    out = {}

    def walk(t, prefix):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(v, prefix + (jax.tree_util.DictKey(k),))
        else:
            out[".".join(_names(prefix[1:]))] = (t, prefix)

    walk(abstract["units"], (jax.tree_util.DictKey("units"),))
    return out


def linear_meta(abstract, mean_abs_in) -> dict:
    """{name: dict(ax, aw, ps, gs, mode)} of every unit linear: the
    static exponents ``make_params`` gives it."""
    from repro.core import DeployedQuantState

    meta = {}
    for name, (t, path) in unit_leaves(abstract).items():
        if isinstance(t, DeployedQuantState):
            ax, aw, ps = linear_exponents(mean_abs_in(_names(path)),
                                          t.w_codes.shape[-2],
                                          t.psum_exps.shape[-2])
            meta[name] = {"ax": ax, "aw": aw, "ps": tuple(ps),
                          "gs": t.spec.psum.gs, "mode": t.spec.psum.mode}
    return meta


def unit_fn(abstract, float_init):
    """jitted ``(key, unit) -> {name: value}``: what ``make_params``
    gives that unit, drawn alone: each linear's INT8 codes and each float
    leaf, under ``unit_leaves``' names."""
    from repro.core import DeployedQuantState

    index = _leaf_index(abstract)
    leaves = unit_leaves(abstract)

    @jax.jit
    def build(key, u):
        out = {}
        for name, (t, path) in leaves.items():
            if isinstance(t, DeployedQuantState):
                i = index[jax.tree_util.keystr(_codes_path(path))]
                out[name] = _codes(_unit_key(key[i], u), t.w_codes.shape[1:])
            else:
                i = index[jax.tree_util.keystr(path)]
                shape = t.shape[1:]
                out[name] = _float(_unit_key(key[i], u), shape,
                                   float_init(_names(path), shape), t.dtype)
        return out

    return build


def float_leaf(key, abstract, float_init, *names):
    """A float leaf outside the units (``embed/table``, ``head/w``,
    ``final_norm/scale``), with the value ``make_params`` gives it."""
    want = tuple(jax.tree_util.DictKey(n) for n in names)
    for i, (p, leaf) in enumerate(leaf_paths(abstract)):
        if tuple(p) == want:
            init = float_init(list(names), leaf.shape)
            return jax.jit(lambda k: _float(k[i], leaf.shape, init,
                                            leaf.dtype))(key)
    raise KeyError(names)


def seed_key(seed: int, n: int = 4096):
    """uint32 [n]: one key per leaf index, mixed from the 64-bit seed."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF

    def mix(x):
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & 0xFFFFFFFF
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & 0xFFFFFFFF
        return x ^ (x >> 16)

    base = mix(lo ^ mix(hi ^ 0x9E3779B9))
    return np.asarray([mix(base ^ mix((i + 1) * 0x9E3779B9 & 0xFFFFFFFF))
                       for i in range(n)], np.uint32)
