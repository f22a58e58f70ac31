"""Distribution utilities: sharding rules, TP/EP serving, gradient compression.

Three layers, one per training/serving concern:

``sharding``
    maps the logical axis names used by every ``*_specs`` tree in
    ``repro.models`` onto concrete mesh axes (with divisibility fallbacks
    and no-axis-reuse), and hosts the ``shard_map``
    wrapper every manual-collective region in the repo goes through.

``tp``
    tensor/expert-parallel *integer serving*: ``shard_deployed`` places
    exported ``DeployedQuantState`` code banks over the "model" axis by
    Algorithm-1 mode (K by whole PSUM tiles for PSQ/W8A8, N for APSQ's
    sequential chain, the expert axis for MoE banks), and the
    ``sharded_*`` executors combine per-device integer partials with
    INT8-on-the-wire collectives (``wire="fp32"`` is the parity-debug
    fallback).  ``ShardedBackend`` in ``repro.exec`` is the entry point;
    ``wire_report`` prices the collectives analytically from the static
    per-layer plan.

``compress``
    the low-bit (INT8 / packed INT4) cross-pod gradient path the trainer
    uses over the DCN ("pod") axis.
"""
from .sharding import (
    DEFAULT_RULES,
    batch_spec,
    optimizer_spec,
    shard_map,
    spec_for,
    tree_specs,
)
from .compress import (
    compress_tree_psum,
    dequantize_grad,
    pack_int4,
    quantize_grad,
    unpack_int4,
)
from .tp import (
    GemmPlan,
    LayerPlan,
    plan_gemm,
    shard_deployed,
    shard_paged_state,
    wire_report,
)

__all__ = [
    "DEFAULT_RULES", "batch_spec", "optimizer_spec", "shard_map",
    "spec_for", "tree_specs", "compress_tree_psum", "dequantize_grad",
    "quantize_grad", "pack_int4", "unpack_int4", "GemmPlan", "LayerPlan",
    "plan_gemm", "shard_deployed", "shard_paged_state", "wire_report",
]
