"""INT8 KV-cache decode attention (PO2 shift scales) — Pallas kernel.

Served in production as the ``kv_attention`` exec op family
(``repro.exec.execute_kv_attention``: ``oracle`` -> ``int8_kv_attention_ref``,
``pallas`` -> ``int8_kv_attention``), which is how the paged serving
engine's decode and prefill chunks read their cache.  The KV tile
(``block_s``) follows from the shapes (``repro.exec.kv_tile``) and spans
many pages; tiles past a slot's length are skipped.
"""
from .kernel import (
    int8_kv_attention_kernel,
    kv_heads_per_step,
    legal_block_s,
)
from .ops import cache_bytes, int8_kv_attention, int8_kv_attention_f32
from .ref import (
    dequantize_kv_po2,
    fp_attention_ref,
    int8_kv_attention_ref,
    quantize_kv_po2,
)

__all__ = [
    "cache_bytes", "dequantize_kv_po2", "fp_attention_ref",
    "int8_kv_attention", "int8_kv_attention_f32",
    "int8_kv_attention_kernel", "int8_kv_attention_ref",
    "kv_heads_per_step", "legal_block_s", "quantize_kv_po2",
]
