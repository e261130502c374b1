"""Jitted public wrapper for the flash-attention kernel.

``flash_attention(...)`` runs the Pallas kernel compiled for the TPU. Off-TPU it
raises unless the caller passes ``interpret=True``, which executes the kernel body
faithfully in the Pallas interpreter for validation. The reference semantics live
in ``ref.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                                   "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,            # (B, H, Sq, d)
    k: jax.Array,            # (B, Hkv, Sk, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=resolve_interpret("flash_attention", interpret))
