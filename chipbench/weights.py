"""The deployed INT8 model made from the seed, on the device, in one call.

The served tree is what ``repro.quant.export`` hands the engine: every
quantized linear becomes a ``DeployedQuantState`` (INT8 weight codes,
activation / weight / partial-sum shift exponents); the embedding table,
the norms and the untied LM head stay in the model's float dtype.  Its
structure and shapes come from ``jax.eval_shape`` of the program's own
``init_lm`` and per-linear export, so no float model is ever held.

Every value is a hash (murmur3's finalizer) of its index within its
array, keyed per leaf from the 64-bit seed and, for scan-stacked units,
per unit: elementwise, so the whole tree is one jitted call that writes
only its outputs, and the reference draws any one unit alone and gets
the same codes.  Weight codes are symmetric on [-15, 15], the embedding
and LM head uniform with the fan-in scale 1/sqrt(d); the exponents are
set as a calibrated deployment's would be (the rule of
``repro.core.init_alpha_from``, 2 * mean|x| / sqrt(127), snapped down to
a power of two), from the activation magnitude each projection sees, so
that activations and partial sums stay in INT8 range.
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
# Mean |x| of the float activation entering each projection, by role:
# RMS-normed inputs and attention outputs have unit scale (mean |N(0,1)|);
# the SwiGLU product silu(g) * i of two unit normals has mean |.| ~ 0.42.
MEAN_ABS_IN = {"mlp_out": 0.42}
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


def _is_linear(t) -> bool:
    from repro.core import QuantState
    return isinstance(t, dict) and "w" in t and isinstance(t.get("qp"),
                                                           QuantState)


def deployed_shapes(cfg):
    """Abstract deployed tree: init's tree with each quantized linear
    replaced by the program's own export of it (shapes only)."""
    from repro.models.model import init_lm
    from repro.quant.export import _export_one

    abstract = jax.eval_shape(lambda k: init_lm(k, cfg),
                              jax.random.PRNGKey(0))

    def walk(t):
        if _is_linear(t):
            w, qp = t["w"], t["qp"]
            fn = _export_one
            if qp.ax.ndim == 1:                       # scan-stacked units
                fn = jax.vmap(_export_one)
            dq, _ = jax.eval_shape(fn, w, qp)
            return {"qp": dq}
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        return t

    return walk(abstract)


def _role(path: tuple) -> str:
    names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
    if "ffn" in names and "wo" in names:
        return "mlp_out"
    return "unit"


def _ax_exp(role: str) -> int:
    m = MEAN_ABS_IN.get(role, MEAN_ABS_UNIT)
    return math.floor(math.log2(2.0 * m / math.sqrt(QMAX)))


def linear_exponents(role: str, k: int, n_p: int):
    """(ax_exp, aw_exp, [psum exps]) of one linear with reduction dim K.

    aw: codes of std CODE_STD times 2^aw give the fan-in scale 1/sqrt(K).
    PSUM tile i holds the running sum of (i+1) K/n_p products; its scale
    is 2 * mean|running| / sqrt(127), in product-scale units."""
    ax = _ax_exp(role)
    aw = round(math.log2(1.0 / (CODE_STD * math.sqrt(k))))
    m = MEAN_ABS_IN.get(role, MEAN_ABS_UNIT)
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


def _leaf_value(key, path, leaf, stacked: bool, n_units: int):
    """Value of one abstract leaf (codes, exponents or a float table)."""
    names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
    field = names[-1]
    dt = leaf.dtype
    if field == "w_codes":
        if stacked:
            return jax.vmap(lambda u: _codes(_unit_key(key, u),
                                             leaf.shape[1:]))(
                jnp.arange(n_units, dtype=jnp.uint32))
        return _codes(key, leaf.shape)
    if field in ("ax_exp", "aw_exp", "psum_exps"):
        return None                                   # filled by the caller
    if field == "scale":                              # norm gains
        return jnp.ones(leaf.shape, dt)
    if field in ("table", "w"):                       # embedding / LM head
        fan = leaf.shape[-1] if field == "table" else leaf.shape[0]
        return _uniform(key, leaf.shape, 1.0 / math.sqrt(fan), dt)
    raise ValueError(f"no rule for leaf {jax.tree_util.keystr(path)}")


def leaf_paths(abstract):
    """[(path, leaf)] in flatten order: the leaf index is its key."""
    return jax.tree_util.tree_leaves_with_path(abstract)


def _build(key, abstract, n_units: int):
    from repro.core import DeployedQuantState

    flat = leaf_paths(abstract)
    index = {jax.tree_util.keystr(p): i for i, (p, _) in enumerate(flat)}

    def walk(t, prefix):
        if isinstance(t, DeployedQuantState):
            kpath = lambda f: prefix + (jax.tree_util.GetAttrKey(f),)  # noqa
            wpath = kpath("w_codes")
            i = index[jax.tree_util.keystr(wpath)]
            stacked = t.ax_exp.ndim == 1
            codes = _leaf_value(key[i], wpath, t.w_codes, stacked, n_units)
            k = t.w_codes.shape[-2]
            n_p = t.psum_exps.shape[-2]
            ax, aw, ps = linear_exponents(_role(prefix), k, n_p)
            ax_e = jnp.full(t.ax_exp.shape, ax, jnp.int32)
            aw_e = jnp.full(t.aw_exp.shape, aw, jnp.int32)
            ps_e = jnp.broadcast_to(
                jnp.asarray(ps, jnp.int32)[:, None],
                t.psum_exps.shape[-2:])
            ps_e = jnp.broadcast_to(ps_e, t.psum_exps.shape)
            return DeployedQuantState(
                w_codes=codes, ax_exp=ax_e, aw_exp=aw_e, psum_exps=ps_e,
                spec=t.spec, name=t.name, out_dims=t.out_dims)
        if isinstance(t, dict):
            return {k: walk(v, prefix + (jax.tree_util.DictKey(k),))
                    for k, v in t.items()}
        i = index[jax.tree_util.keystr(prefix)]
        return _leaf_value(key[i], prefix, t, False, n_units)

    return walk(abstract, ())


def make_params(seed: int, cfg, abstract=None):
    """The whole deployed tree on the default device, in one jitted call."""
    if abstract is None:
        abstract = deployed_shapes(cfg)
    return jax.jit(lambda k: _build(k, abstract, cfg.n_units))(
        seed_key(seed))


def _unit_linears(abstract):
    """{dotted name: (abstract DeployedQuantState, path)} of the scan
    units' linears, e.g. ``0.mix.wq``."""
    from repro.core import DeployedQuantState

    out = {}

    def walk(t, prefix, names):
        if isinstance(t, DeployedQuantState):
            out[".".join(names[:-1])] = (t, prefix)
        elif isinstance(t, dict):
            for k, v in t.items():
                walk(v, prefix + (jax.tree_util.DictKey(k),), names + [k])

    walk(abstract["units"], (jax.tree_util.DictKey("units"),), [])
    return out


def linear_meta(abstract) -> dict:
    """{name: dict(ax, aw, ps, gs, mode)} of every unit linear: the
    static exponents ``make_params`` gives it."""
    meta = {}
    for name, (t, prefix) in _unit_linears(abstract).items():
        ax, aw, ps = linear_exponents(_role(prefix), t.w_codes.shape[-2],
                                      t.psum_exps.shape[-2])
        meta[name] = {"ax": ax, "aw": aw, "ps": tuple(ps),
                      "gs": t.spec.psum.gs, "mode": t.spec.psum.mode}
    return meta


def unit_codes_fn(abstract):
    """jitted ``(key, unit) -> {name: int8 codes}``: the codes
    ``make_params`` gives that unit's linears, drawn alone."""
    index = {jax.tree_util.keystr(p): i
             for i, (p, _) in enumerate(leaf_paths(abstract))}
    lins = _unit_linears(abstract)

    @jax.jit
    def build(key, u):
        out = {}
        for name, (t, prefix) in lins.items():
            wpath = prefix + (jax.tree_util.GetAttrKey("w_codes"),)
            i = index[jax.tree_util.keystr(wpath)]
            out[name] = _codes(_unit_key(key[i], u), t.w_codes.shape[1:])
        return out

    return build


def float_leaf(key, abstract, *names):
    """A float leaf outside the units (``embed/table``, ``head/w``,
    ``final_norm/scale``), with the value ``make_params`` gives it."""
    flat = leaf_paths(abstract)
    want = tuple(jax.tree_util.DictKey(n) for n in names)
    for i, (p, leaf) in enumerate(flat):
        if tuple(p) == want:
            return jax.jit(lambda k: _leaf_value(k[i], p, leaf, False, 0))(
                key)
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
