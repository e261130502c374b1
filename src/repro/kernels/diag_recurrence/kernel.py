"""Pallas TPU kernel for the diagonal linear recurrence h_t = a_t·h_{t-1} + b_t.

Serves both the Mamba-1 selective scan (channels = d_inner·ssm_state, flattened) and
the RG-LRU (channels = lru_width). Grid ``(B, n_chunks)`` with the chunk axis
innermost and sequential; the inter-chunk state is carried in VMEM scratch (persists
across sequential grid steps on TPU), so HBM traffic is exactly one read of (a, b) and
one write of h — the memory-bound optimum. Within a chunk the recurrence is a
``fori_loop`` over rows of the VMEM-resident block: on TPU this is a (chunk_len)-step
VPU chain over lanes-of-C vectors, which pipelines with the next block's DMA.

Channel blocking (grid dim 2) keeps the block (chunk, block_c) within VMEM for large
C (falcon-mamba: C = d_inner·N = 131072 fp32 -> block_c = 2048 gives 2 MB blocks).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_CHUNK = 128
DEFAULT_BLOCK_C = 2048


def _recurrence_kernel(a_ref, b_ref, h0_ref, h_ref, carry, *, chunk: int):
    j = pl.program_id(1)  # chunk index (sequential)

    @pl.when(j == 0)
    def _init():
        carry[...] = h0_ref[0]

    # Index the refs, not a loaded block: a traced row of a loaded value
    # lowers to dynamic_slice, which Pallas TPU does not implement.
    def body(t, h):                                   # h: (1, bc)
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]
        h_ref[0, pl.ds(t, 1), :] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, carry[...])
    carry[...] = h


def diag_recurrence_pallas(
    a: jax.Array,            # (B, S, C) fp32
    b: jax.Array,            # (B, S, C)
    h0: jax.Array,           # (B, C)
    *,
    chunk: int = DEFAULT_CHUNK,
    block_c: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (h_all (B, S, C), h_final (B, C))."""
    B, S, C = a.shape
    chunk = max(1, min(chunk, S))
    block_c = max(8, min(block_c, C))
    pad_s = (-S) % chunk
    pad_c = (-C) % block_c
    if pad_s or pad_c:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_c)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_c)))
    if pad_c:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_c)))
    Sp, Cp = a.shape[1], a.shape[2]
    n_chunks, n_cblocks = Sp // chunk, Cp // block_c
    h0 = h0[:, None, :]     # (B, 1, Cp): a (1, block_c) block spans a whole dim

    kernel = functools.partial(_recurrence_kernel, chunk=chunk)
    h_all = pl.pallas_call(
        kernel,
        grid=(B * n_cblocks, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_c),
                         lambda bc, j, n=n_cblocks: (bc // n, j, bc % n)),
            pl.BlockSpec((1, chunk, block_c),
                         lambda bc, j, n=n_cblocks: (bc // n, j, bc % n)),
            pl.BlockSpec((1, 1, block_c),
                         lambda bc, j, n=n_cblocks: (bc // n, 0, bc % n)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_c),
                               lambda bc, j, n=n_cblocks: (bc // n, j, bc % n)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Cp), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    h_all = h_all[:, :S, :C]
    return h_all, h_all[:, -1]
