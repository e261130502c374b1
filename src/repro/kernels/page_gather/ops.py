"""Jitted public wrapper for the paged-gather kernel (off-TPU only with
``interpret=True``)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.backend import resolve_interpret
from repro.kernels.page_gather.kernel import page_gather_pallas


@partial(jax.jit, static_argnames=("interpret",))
def page_gather(pool: jax.Array, page_ids: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """``pool[page_ids]`` for a ``(P, R, L)`` page pool -> ``(K, R, L)``."""
    return page_gather_pallas(
        pool, page_ids, interpret=resolve_interpret("page_gather", interpret))
