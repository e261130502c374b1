"""Jitted public wrapper for the flash-decode kernel (off-TPU only with
``interpret=True``)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.backend import resolve_interpret
from repro.kernels.decode_attention.kernel import decode_attention_pallas


@partial(jax.jit, static_argnames=("softcap", "scale", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,            # (B, H, d)
    k_cache: jax.Array,      # (B, Hkv, S, d)
    v_cache: jax.Array,
    valid: jax.Array,        # (S,) bool
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    return decode_attention_pallas(
        q, k_cache, v_cache, valid, softcap=softcap, scale=scale, block_k=block_k,
        interpret=resolve_interpret("decode_attention", interpret))
