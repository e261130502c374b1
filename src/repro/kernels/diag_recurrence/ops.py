"""Jitted public wrapper for the diagonal-recurrence kernel (off-TPU only with
``interpret=True``)."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax

from repro.kernels.backend import resolve_interpret
from repro.kernels.diag_recurrence.kernel import diag_recurrence_pallas


@partial(jax.jit, static_argnames=("chunk", "block_c", "interpret"))
def diag_recurrence(
    a: jax.Array, b: jax.Array, h0: jax.Array,
    *, chunk: int = 128, block_c: int = 2048, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    return diag_recurrence_pallas(
        a, b, h0, chunk=chunk, block_c=block_c,
        interpret=resolve_interpret("diag_recurrence", interpret))
