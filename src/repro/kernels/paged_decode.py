"""Paged-KV decode attention kernel (Pallas / TPU) — block-table-indexed.

Continuous-batching servers cannot afford one dense, max-length KV buffer
per request: admission would be bounded by the *longest possible* sequence
instead of the tokens actually resident. The serving subsystem
(``repro.serving``) therefore stores KV in a shared pool of fixed-size
pages; each sequence owns an ordered list of page ids (its *block table*)
and appends tokens to its last partially-filled page.

This kernel consumes that layout directly. The block table, the
per-sequence valid lengths and the layer index are **scalar-prefetched**
(``pltpu.PrefetchScalarGridSpec``) so the KV BlockSpec index map can chase
the table: grid step ``(row, j)`` DMAs physical page ``table[b, j]`` of
layer ``layer`` — a gather at page granularity with zero host-side
reshuffling, the TPU analogue of vLLM's PagedAttention. The serving model
keeps every layer's pool stacked in one buffer ``(L, Hkv, P, page_size,
D)`` and passes it whole with the layer to read, so no layer's pool is
ever sliced out of it; a single-layer pool ``(Hkv, P, page_size, D)``
(the tuner's and the oracles' form) is read as a stack of one.

Tunables (registered as ``paged_decode`` in the kernel registry):

    page_size : rows per physical page — the pool's allocation granule.
                Small pages waste less memory on ragged tails but shrink the
                DMA size per grid step; the sweet spot moves with chip DMA
                latency, so it is a first-class tunable that the serving
                launcher reads back when sizing the pool.
    block_kv  : KV rows scored per accumulation step, a multiple of
                ``page_size`` — pages are fetched individually but the
                online-softmax loop skips whole ``block_kv`` super-blocks
                past ``kv_len`` (admission keeps sequences ragged, so the
                skip granularity matters).
    pack_gqa  : as in ``gqa_decode`` — True packs the ``Hq // Hkv`` query
                heads sharing a KV head into the sublane dim (each page
                read once per group); False gives every query head its own
                grid row (more parallelism, ``group``× the page traffic).

Unlike ``decode_attention``/``gqa_decode`` there is no ``k_splits``
partial-combine: sequences in a paged pool are short-to-medium ragged
(long prefixes get chunk-prefilled), and the row grid ``B*Hkv`` of a
continuous batch already fills the cores.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30
LANES = 128


def _paged_kernel(tbl_ref, len_ref, layer_ref,     # scalar-prefetched
                  q_ref, k_ref, v_ref,             # inputs (k/v: one page)
                  *rest,                           # [ks, vs,] o, scratch...
                  scale: float, page_size: int, pages_per_block: int,
                  heads_per_b: int, capacity: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    r = pl.program_id(0)                 # which (batch, head) row
    sj = pl.program_id(1)                # which block_kv super-block
    pj = pl.program_id(2)                # page within the super-block
    n_super = pl.num_programs(1)

    @pl.when((sj == 0) & (pj == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Clamp to the pool-backed capacity: kv_len may exceed it transiently
    # (caller bug surfaced as masking, not OOB reads of foreign pages).
    b = r // heads_per_b
    kv_len = jnp.minimum(len_ref[b], capacity)
    # Skip at block_kv granularity (the whole super-block is past the valid
    # prefix), then mask the in-page tail positionally.
    run = (sj * pages_per_block * page_size) < kv_len
    k_start = (sj * pages_per_block + pj) * page_size

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (g, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (g, page_size)
        if quantized:
            # int8 pages (the kv8 policy): per-token scales arrive as one
            # lane-major (1, page_size) row, so they dequantize the scores
            # column-wise instead of the (page_size, D) rows of K.
            s = s * ks_ref[0, 0]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # Likewise V's per-token scales weight the probabilities.
        pv = p * vs_ref[0, 0] if quantized else p
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when((sj == n_super - 1) & (pj == pages_per_block - 1))
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)   # kv_len==0 row -> zeros
        # Only q's head dim leaves the kernel: the pool's lanes past it
        # hold zeros.
        o_ref[0] = (acc_ref[...] / safe_l)[:, :o_ref.shape[-1]]


def pool_head_dim(head_dim: int) -> int:
    """The head dim a page pool stores: ``head_dim`` rounded up to whole
    128-lane rows, zeros past it. The TPU's default layout of a pool whose
    last dim is narrower than the lanes puts the page slots minor, which
    the kernels cannot read in place; at whole lanes the default layout is
    the row-major one they read. It costs no more memory than row-major
    at the narrower width, which pads each row to the lanes anyway."""
    return -(-head_dim // LANES) * LANES


def stack_pools(k_pages, v_pages, k_scales, v_scales, layer):
    """The kernels' operand form of a pool: stacked ``(L, Hkv, P,
    page_size, D)`` pages with scales ``(L, Hkv, P, 1, page_size)``, and
    the layer to read as a ``(1,)`` int32 array. A single-layer pool
    ``(Hkv, P, page_size, D)`` with ``(Hkv, P, page_size)`` scales becomes
    a stack of one read at layer 0."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = (sc[None, :, :, None, :]
                                  for sc in (k_scales, v_scales))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return k_pages, v_pages, k_scales, v_scales, layer


def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, kv_len: jnp.ndarray, *,
                 layer=0,
                 k_scales: Optional[jnp.ndarray] = None,
                 v_scales: Optional[jnp.ndarray] = None,
                 scale: Optional[float] = None,
                 block_kv: Optional[int] = None,
                 pack_gqa: bool = True,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Block-table-indexed decode attention over a shared page pool.

    q            (B, Hq, D)   one query token per sequence
    k_pages      (Hkv, P, page_size, D)   the pool (all sequences share it),
    v_pages      or every layer's pool stacked, (L, Hkv, P, page_size, D),
                 of which layer ``layer`` (an int32 scalar) is read in place;
                 the pool's D may exceed q's (``pool_head_dim``: zeros past
                 q's head dim)
    block_tables (B, max_pages) int32     logical block j of seq b -> page id
    kv_len       (B,) int32               valid tokens per sequence
    k_scales     optional — required iff the pools are int8 (the kv8
    v_scales     policy): per-token f32 dequant scales chased through the
                 same block tables as the pages, (Hkv, P, page_size) beside
                 a single-layer pool, (L, Hkv, P, 1, page_size) beside a
                 stacked one

    ``page_size`` is a property of the pool layout (``k_pages.shape[-2]``);
    ``block_kv`` must be a multiple of it (default: one page per block).
    Rows with ``kv_len == 0`` (inactive batch slots) return zeros.
    """
    B, Hq, head_dim = q.shape
    quantized = k_pages.dtype == jnp.int8
    assert quantized == (k_scales is not None) == (v_scales is not None), \
        "int8 pools require k_scales/v_scales; float pools forbid them"
    k_pages, v_pages, k_scales, v_scales, layer = stack_pools(
        k_pages, v_pages, k_scales, v_scales, layer)
    _, Hkv, n_pages, page_size, D = k_pages.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = head_dim ** -0.5
    # Zero lanes of q against the pool's zero lanes add nothing.
    q = jnp.pad(q, ((0, 0), (0, 0), (0, D - head_dim)))
    if block_kv is None:
        block_kv = page_size
    assert block_kv % page_size == 0, (block_kv, page_size)
    pages_per_block = block_kv // page_size

    max_pages = block_tables.shape[1]
    capacity = max_pages * page_size
    n_super = -(-max_pages // pages_per_block)
    t_pages = n_super * pages_per_block
    if t_pages != max_pages:
        # Pad with page 0 (the pool's reserved scratch page): the index map
        # must always produce a resident page; padded positions are masked
        # by kv_len before they can score.
        block_tables = jnp.pad(block_tables, ((0, 0),
                                              (0, t_pages - max_pages)))

    g = group if pack_gqa else 1
    rows = B * Hkv if pack_gqa else B * Hq
    heads_per_b = Hkv if pack_gqa else Hq
    qg = q.reshape(rows, g, D)

    def kv_head(r):
        return r % Hkv if pack_gqa else (r % Hq) // group

    def kv_index(r, sj, pj, tbl, lens, lyr, ppb=pages_per_block):
        return (lyr[0], kv_head(r), tbl[r // heads_per_b, sj * ppb + pj],
                0, 0)

    def row_index(r, sj, pj, tbl, lens, lyr):
        return (r, 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, D), row_index),
        pl.BlockSpec((pl.squeezed, 1, 1, page_size, D), kv_index),
        pl.BlockSpec((pl.squeezed, 1, 1, page_size, D), kv_index),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # Scale pools are laid out (L, Hkv, P, 1, page_size): a
        # (1, page_size) block spans whole trailing dims, which Mosaic
        # requires of a block that is not (8, 128)-aligned.
        in_specs += [pl.BlockSpec((pl.squeezed, 1, 1, 1, page_size),
                                  kv_index)] * 2
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, n_super, pages_per_block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, head_dim), row_index),
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        pages_per_block=pages_per_block, heads_per_b=heads_per_b,
        capacity=capacity, quantized=quantized)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, g, head_dim), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="paged_decode",
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32), layer,
      *operands)
    return o.reshape(B, Hq, head_dim).astype(q.dtype)
