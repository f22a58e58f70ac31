"""Execution-backend layer: how the integer op families are computed.

One registry (``oracle`` | ``pallas`` | ``auto``) behind two entry points:
``execute_gemm(deployed_layer, x)`` for deployed integer GEMMs and
``execute_kv_attention(q, k_codes, v_codes, ...)`` for decode attention
over an INT8 KV cache — see ``backends.py`` for the design.
"""
from .backends import (
    AutoBackend,
    DEFAULT_BACKEND,
    ExecBackend,
    OracleBackend,
    PallasBackend,
    ShardedBackend,
    available_backends,
    backend_parity_check,
    execute_expert_gemm,
    execute_gemm,
    execute_kv_attention,
    get_backend,
    kv_tile,
    quantize_activations,
    register_backend,
)

__all__ = [
    "AutoBackend", "DEFAULT_BACKEND", "ExecBackend", "OracleBackend",
    "PallasBackend", "ShardedBackend", "available_backends",
    "backend_parity_check",
    "execute_expert_gemm", "execute_gemm", "execute_kv_attention",
    "get_backend", "kv_tile", "quantize_activations",
    "register_backend",
]
