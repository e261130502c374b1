"""Pure-jnp oracle for the paged weight-restore gather."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def page_gather_ref(pool: jax.Array, page_ids: jax.Array) -> jax.Array:
    """pool: (P, R, L); page_ids: (K,) int32 -> out (K, R, L) = pool[page_ids]."""
    return jnp.take(pool, page_ids, axis=0)
