"""Pallas TPU kernels for the perf-critical compute/data-movement hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), <name>/ops.py (jitted public wrapper: compiled for the TPU; anywhere
else it raises unless the caller passes ``interpret=True``), and <name>/ref.py
(pure-jnp oracle used by the allclose test sweeps).

  * flash_attention — blockwise online-softmax prefill attention
                      (causal / SWA / softcap / GQA)
  * decode_attention — single-token flash decode over long (ring) KV caches
  * diag_recurrence — chunked diagonal linear recurrence (Mamba-1 / RG-LRU scan)
  * page_gather     — paged weight-restore gather (WarmSwap pool hot path,
                      scalar-prefetch DMA pattern)
"""
from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.diag_recurrence import diag_recurrence, diag_recurrence_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.page_gather import page_gather, page_gather_ref

__all__ = [
    "flash_attention", "attention_ref",
    "decode_attention", "decode_attention_ref",
    "diag_recurrence", "diag_recurrence_ref",
    "page_gather", "page_gather_ref",
]
