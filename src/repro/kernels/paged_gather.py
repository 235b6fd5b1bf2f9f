"""Copy sequences' pages out of a stacked page pool (Pallas / TPU).

Chunked prefill attends a chunk's queries over the whole resident prefix
of its sequence, so it needs that prefix dense. The serving model keeps
every layer's pages in one stacked buffer ``(L, Hkv, P, page_size, D)``;
an XLA gather of one layer's pages from it makes the TPU compiler copy
or relayout the whole pool when ``D`` is narrower than the 128 lanes (it
splits the gather into slices of the operand, or moves the page-slot axis
minor). This kernel reads only the pages the block tables name, in place,
as ``paged_decode`` does: the table and the layer are scalar-prefetched
and the input index map chases them, one ``(page_size, D)`` block per
grid step. It is a plain copy, so the dense prefix holds exactly the
pool's values.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _copy_kernel(tbl_ref, layer_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def paged_gather(pages: jnp.ndarray, block_tables: jnp.ndarray, layer, *,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Each sequence's pages of layer ``layer`` of a stacked pool.

    pages        (L, Hkv, P, r, c) — K/V pages, (r, c) = (page_size, D),
                 or per-token scale pages, (r, c) = (1, page_size)
    block_tables (B, max_pages) int32    logical block j of seq b -> page id
    layer        int32 scalar

    Returns (B, Hkv, max_pages, r, c): page ``block_tables[b, j]`` of head
    h at ``[b, h, j]``.
    """
    _, Hkv, _, r, c = pages.shape
    B, n_blocks = block_tables.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_blocks),
        in_specs=[pl.BlockSpec(
            (pl.squeezed, 1, 1, r, c),
            lambda b, h, j, tbl, lyr: (lyr[0], h, tbl[b, j], 0, 0))],
        out_specs=pl.BlockSpec(
            (pl.squeezed, 1, 1, r, c),
            lambda b, h, j, tbl, lyr: (b, h, j, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, n_blocks, r, c),
                                       pages.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_gather",
    )(block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), pages)
