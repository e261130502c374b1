"""Pallas TPU paged-gather: assemble contiguous weights from pooled pages.

The device-side hot path of WarmSwap restore: the dependency pool keeps parameter
pages in a big HBM buffer shared by all tenants; instance bring-up gathers each
tenant's page list into its contiguous parameter buffers. This is pure data movement,
so the kernel is shaped around the DMA engine: grid ``(K, R // block_rows)`` over
destination pages and row blocks, with the *scalar-prefetched* page-id list driving
the input index map — the DMA for the next block issues while the current one copies
(double buffering), sustaining HBM bandwidth.

A page is an ``(R, L)`` tile stack (``L`` = 128 lanes for a tile-aligned pool), so
every block's last two dimensions are either tile-aligned or the whole page, as the
TPU lowering requires. Scalar prefetch (``pltpu.PrefetchScalarGridSpec``) is exactly
the TPU idiom for this "pointer-chase then stream" pattern (same as paged attention's
block tables).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

#: Bytes per block. In and out are double-buffered, so the kernel holds 4x
#: this in VMEM, well inside the default scoped limit, whatever the page size.
BLOCK_BYTES = 1 << 20


def _gather_kernel(page_ids_ref, pool_ref, out_ref):
    # pool_ref block was selected by the index map via the prefetched page id;
    # the body is a VMEM->VMEM copy.
    out_ref[...] = pool_ref[...]


def page_gather_pallas(
    pool: jax.Array,         # (P, R, L)
    page_ids: jax.Array,     # (K,) int32
    *,
    interpret: bool = False,
) -> jax.Array:
    if pool.ndim != 3:
        raise ValueError(f"pool must be (pages, rows, lanes), got {pool.shape}")
    P, R, L = pool.shape
    K = page_ids.shape[0]
    rows = min(R, max(8, BLOCK_BYTES // (L * pool.dtype.itemsize)))
    if R % rows:
        rows = R
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K, R // rows),
        in_specs=[
            pl.BlockSpec((1, rows, L), lambda i, j, ids: (ids[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, L), lambda i, j, ids: (i, j, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, R, L), pool.dtype),
        interpret=interpret,
    )(page_ids, pool)
