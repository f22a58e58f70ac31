"""Logical-axis -> mesh-axis sharding rules.

Every ``init_*`` function in the model zoo has a parallel ``*_specs``
function returning *logical axis names* per param (same tree structure).
This module maps those names onto the production mesh:

  * ``spec_for``       — one leaf: logical axes + shape -> PartitionSpec,
    with divisibility checks and no-axis-reuse (first dim wins);
  * ``tree_specs``     — whole tree, structure-aware (understands the
    ``QuantState`` quantizer pytree from ``repro.core``);
  * ``batch_spec``     — activation batch dim over ("pod","data") with
    divisibility fallback to ("data",) then replication;
  * ``optimizer_spec`` — ZeRO-1: shard the first still-replicated,
    pod-divisible dim of an optimizer moment over the DCN "pod" axis.

Rules are overridable per call (``rules={...}``); candidates are tried in
order and skipped when the mesh lacks the axis, the axis is already used
by an earlier dim, or the dim size is not divisible by the axis size.
"""
from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with this repo's defaults.

    Every shard_map call site in the repo goes through here.
    ``axis_names``: the axes the body is *manual* over (e.g. {"pod"} for
    DCN gradient compression, leaving "data"/"model" to GSPMD).  None
    means manual over every mesh axis.
    """
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)

# logical name -> ordered candidate mesh-axis groups (each a tuple of axes)
DEFAULT_RULES = {
    "batch": (("pod", "data"), ("data",)),
    "embed": (("data",),),          # FSDP: reduction/K dims over "data"
    "embed_out": (("model",),),
    "ff": (("model",),),            # TP: output/N dims over "model"
    "qheads": (("model",),),
    "kvheads": (("model",),),
    "kvheads_cache": (("model",),),
    "heads": (("model",),),
    "vocab": (("model",),),
    "vocab_in": (("data",),),
    "expert": (("model",),),        # EP
    "rnn": (("model",),),
    "norm": (),
    "layers": (),                   # scan-stacked leading axis: replicated
    "ff_unsharded": (),             # MoE expert N dim (expert axis carries EP)
}


def spec_for(axes: tuple, shape: tuple, mesh, rules: dict | None = None) -> P:
    """PartitionSpec for one param from its logical axes and shape.

    ``axes`` entries are logical names or None (replicated).  Each mesh
    axis is used at most once per spec; a candidate is accepted only when
    its total size divides the dim.
    """
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    used: set = set()
    out = []
    for i, name in enumerate(axes):
        choice = None
        for cand in (merged.get(name, ()) if name is not None else ()):
            cand = tuple(cand) if isinstance(cand, (tuple, list)) else (cand,)
            if any(a not in mesh.axis_names or a in used for a in cand):
                continue
            size = math.prod(mesh.shape[a] for a in cand)
            dim = shape[i] if i < len(shape) else 0
            if dim > 0 and dim % size == 0:
                choice = cand
                break
        if choice:
            used.update(choice)
            out.append(choice if len(choice) > 1 else choice[0])
        else:
            out.append(None)
    return P(*out)


def batch_spec(mesh, batch: int, extra_dims: int = 0) -> P:
    """Spec for a [batch, ...] activation: batch over ("pod","data")."""
    return spec_for(("batch",) + (None,) * extra_dims,
                    (batch,) + (1,) * extra_dims, mesh)


def optimizer_spec(spec: P, shape: tuple, mesh) -> P:
    """ZeRO-1: shard the first replicated pod-divisible dim over "pod"."""
    if "pod" not in mesh.axis_names:
        return spec
    pod = mesh.shape["pod"]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, e in enumerate(entries):
        if e is None and shape[i] % pod == 0 and shape[i] > 1:
            entries[i] = "pod"
            break
    return P(*entries)


def tree_specs(spec_tree, shape_tree, mesh, rules: dict | None = None):
    """Map a logical-spec tree over a shape tree -> PartitionSpec tree.

    The result has the *params* tree structure (so it can be mapped to
    NamedShardings and fed to jit in/out_shardings directly).  Quantizer
    state (``repro.core.QuantState``) in the shape tree is paired with the
    ``{"aw","ax","ap"}`` spec dict produced by ``linear_specs``.

    Exported trees work too: a ``DeployedQuantState`` under ``"qp"`` /
    ``"qp_head"`` / ``"qp_<expert>"`` inherits its *weight codes* spec
    from the sibling float-weight entry the export dropped (``"w"``, the
    embedding ``"table"`` — transposed for the tied head — or the expert
    bank name), with every exponent leaf replicated; spec-tree keys whose
    params were consumed by the export are simply skipped.  For the
    serving-side plan (K by whole PSUM tiles, N for APSQ, expert axis for
    MoE banks) use ``repro.dist.tp.shard_deployed`` instead — this path
    exists so generic spec tooling keeps working on deployed trees.
    """
    from repro.core import DeployedQuantState, QuantState

    def deployed(sp_dict, key, dq):
        qspec = sp_dict.get(key) if isinstance(sp_dict, dict) else None
        if isinstance(qspec, dict) and "w_codes" in qspec:  # explicit form
            waxes = qspec["w_codes"]
        else:
            wkey = ("w" if key == "qp"
                    else "table" if key == "qp_head" else key[3:])
            waxes = sp_dict.get(wkey) if isinstance(sp_dict, dict) else None
            if key == "qp_head" and isinstance(waxes, tuple):
                waxes = tuple(reversed(waxes))  # codes are [d, vocab]
        wspec = (spec_for(waxes, tuple(dq.w_codes.shape), mesh, rules)
                 if isinstance(waxes, tuple) else P())
        return dataclasses.replace(
            dq, w_codes=wspec, ax_exp=P(), aw_exp=P(),
            psum_exps=None if dq.psum_exps is None else P())

    def rec(sp, sh, path):
        if isinstance(sp, tuple):
            return spec_for(sp, tuple(sh.shape), mesh, rules)
        if isinstance(sh, QuantState):
            sub = ({f: getattr(sp, f) for f in ("aw", "ax", "ap")}
                   if isinstance(sp, QuantState) else sp)
            return dataclasses.replace(
                sh,
                aw=rec(sub["aw"], sh.aw, path + ("aw",)),
                ax=rec(sub["ax"], sh.ax, path + ("ax",)),
                ap=(rec(sub.get("ap"), sh.ap, path + ("ap",))
                    if sh.ap is not None else None),
            )
        if isinstance(sp, dict):
            missing = set(sh) - set(sp) if isinstance(sh, dict) else set()
            if missing:
                raise KeyError(f"spec tree missing {sorted(missing)} "
                               f"at {'/'.join(path) or '<root>'}")
            if isinstance(sh, dict):
                # Iterate the PARAMS keys: export drops float banks, so
                # stale spec-tree entries ("w", "wi", ...) are skipped.
                return {k: (deployed(sp, k, v)
                            if isinstance(v, DeployedQuantState)
                            else rec(sp[k], v, path + (k,)))
                        for k, v in sh.items()}
            return {k: rec(v, sh[k], path + (k,)) for k, v in sp.items()}
        if sp is None:
            return None if sh is None else P()
        raise TypeError(f"unsupported spec node {type(sp).__name__} "
                        f"at {'/'.join(path) or '<root>'}")

    return rec(spec_tree, shape_tree, ())
