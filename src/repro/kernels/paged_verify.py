"""Paged-KV batched-verify attention kernel (Pallas / TPU).

Speculative decoding's verify step is the new kernel shape the paper's
thesis predicts hand-tuned libraries will miss: score **K draft
positions per sequence in one launch** against the same shared page
pool that ``paged_decode`` serves. Each sequence's query block carries
K consecutive positions — the last committed token plus K-1 drafted
continuations — and position ``t`` must attend the resident prefix
*plus the drafts before it*: a ragged ``kv_len + K`` causal tail, not
a rectangle and not single-token decode.

Layout: the draft positions ride the **sublane dimension** next to the
packed GQA group — the query block per grid row is ``(K * g, D)`` with
sublane ``s = t * g + gi`` (draft position ``t``, group head ``gi``).
One page read scores all K positions of all g heads, so the verify
step costs one ``paged_decode``-shaped pass, not K of them.

Tunables (registered as ``paged_verify``):

    draft_k   : draft width K — how many positions one launch scores.
                Pinned by the serving layer's speculation depth the same
                way ``page_size`` is pinned by the pool layout; deployment
                tuning sweeps it so the shipped DB can size the drafter.
    page_size : rows per physical page (pool layout pin, as paged_decode).
    block_kv  : KV rows per accumulation super-block (multiple of
                page_size) — the ragged-skip granularity.
    pack_gqa  : pack the Hq//Hkv group heads into the sublane dim beside
                K (True) or give each query head its own grid row (False).

Masking: ``kv_len`` counts valid tokens *including* the K scattered
draft positions. Query ``t`` (absolute position ``kv_len - K + t``)
attends ``k_pos <= kv_len - K + t``; the probability block is zeroed
outside the mask (not just NEG_INF'ed) so fully-masked query rows —
inactive slots and ``kv_len < K`` underfull tails — produce exact
zeros instead of a softmax over garbage.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret
from repro.kernels.paged_decode import stack_pools

NEG_INF = -1e30
LANES = 128


def _verify_kernel(tbl_ref, len_ref, layer_ref,    # scalar-prefetched
                   q_ref, k_ref, v_ref,            # inputs (k/v: one page)
                   *rest,                          # [ks, vs,] o, scratch...
                   scale: float, page_size: int, pages_per_block: int,
                   heads_per_b: int, capacity: int, quantized: bool,
                   draft_k: int, group: int):
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

    b = r // heads_per_b
    kv_len = jnp.minimum(len_ref[b], capacity)
    run = (sj * pages_per_block * page_size) < kv_len
    k_start = (sj * pages_per_block + pj) * page_size

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (K*g, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (K*g, page_size)
        if quantized:
            # Lane-major (1, page_size) per-token scales dequantize the
            # score columns (and, below, the probability columns for V).
            s = s * ks_ref[0, 0]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Sublane s = t * group + gi: recover the draft position t. Query t
        # sits at absolute position kv_len - K + t and attends causally.
        draft_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        q_pos = kv_len - draft_k + draft_t
        mask = k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Zero (not NEG_INF-softmax) masked probabilities: a fully masked
        # query row then accumulates l == 0 and finalizes to exact zeros.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[0, 0] if quantized else p
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when((sj == n_super - 1) & (pj == pages_per_block - 1))
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)   # masked row -> zeros
        # Only q's head dim leaves the kernel (see paged_decode).
        o_ref[0] = (acc_ref[...] / safe_l)[:, :o_ref.shape[-1]]


def paged_verify(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, kv_len: jnp.ndarray, *,
                 layer=0,
                 k_scales: Optional[jnp.ndarray] = None,
                 v_scales: Optional[jnp.ndarray] = None,
                 scale: Optional[float] = None,
                 block_kv: Optional[int] = None,
                 pack_gqa: bool = True,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Block-table-indexed K-position verify attention over a page pool.

    q            (B, K, Hq, D)  K consecutive query positions per sequence
    k_pages      (Hkv, P, page_size, D)   the shared pool, or every
    v_pages      layer's pool stacked (L, Hkv, P, page_size, D), read in
                 place at layer ``layer``; D may exceed q's (see
                 ``paged_decode``)
    block_tables (B, max_pages) int32
    kv_len       (B,) int32  valid tokens per sequence **including** the K
                 scattered draft positions: query t attends
                 ``k_pos <= kv_len - K + t``
    k_scales     optional f32 per-token dequant scales, (Hkv, P, page_size)
    v_scales     or stacked (L, Hkv, P, 1, page_size) — required iff the
                 pools are int8 (the kv8 policy)

    Rows with ``kv_len == 0`` (inactive slots) return zeros, as do query
    positions whose causal window is empty (``kv_len < K`` tails).
    """
    B, K, Hq, head_dim = q.shape
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
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, D - head_dim)))
    if block_kv is None:
        block_kv = page_size
    assert block_kv % page_size == 0, (block_kv, page_size)
    pages_per_block = block_kv // page_size

    max_pages = block_tables.shape[1]
    capacity = max_pages * page_size
    n_super = -(-max_pages // pages_per_block)
    t_pages = n_super * pages_per_block
    if t_pages != max_pages:
        block_tables = jnp.pad(block_tables, ((0, 0),
                                              (0, t_pages - max_pages)))

    g = group if pack_gqa else 1
    rows = B * Hkv if pack_gqa else B * Hq
    heads_per_b = Hkv if pack_gqa else Hq
    # Sublane layout (K * g, D): draft position outermost, group head
    # innermost — sublane s = t * g + gi.
    qg = (q.reshape(B, K, Hkv, g, D) if pack_gqa
          else q.reshape(B, K, Hq, 1, D))
    qg = jnp.moveaxis(qg, 1, 2).reshape(rows, K * g, D)

    def kv_head(r):
        return r % Hkv if pack_gqa else (r % Hq) // group

    def kv_index(r, sj, pj, tbl, lens, lyr, ppb=pages_per_block):
        return (lyr[0], kv_head(r), tbl[r // heads_per_b, sj * ppb + pj],
                0, 0)

    def row_index(r, sj, pj, tbl, lens, lyr):
        return (r, 0, 0)

    in_specs = [
        pl.BlockSpec((1, K * g, D), row_index),
        pl.BlockSpec((pl.squeezed, 1, 1, page_size, D), kv_index),
        pl.BlockSpec((pl.squeezed, 1, 1, page_size, D), kv_index),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # (L, Hkv, P, 1, page_size) scale pools: see paged_decode.
        in_specs += [pl.BlockSpec((pl.squeezed, 1, 1, 1, page_size),
                                  kv_index)] * 2
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, n_super, pages_per_block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K * g, head_dim), row_index),
        scratch_shapes=[
            pltpu.VMEM((K * g, D), jnp.float32),
            pltpu.VMEM((K * g, LANES), jnp.float32),
            pltpu.VMEM((K * g, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _verify_kernel, scale=scale, page_size=page_size,
        pages_per_block=pages_per_block, heads_per_b=heads_per_b,
        capacity=capacity, quantized=quantized, draft_k=K,
        group=g)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, K * g, head_dim), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="paged_verify",
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32), layer,
      *operands)
    D = head_dim
    o = o.reshape(rows, K, g, D)
    if pack_gqa:
        o = jnp.moveaxis(o.reshape(B, Hkv, K, g, D), 2, 1)
        o = o.reshape(B, K, Hq, D)
    else:
        o = jnp.moveaxis(o.reshape(B, Hq, K, 1, D)[..., 0, :], 2, 1)
    return o.astype(q.dtype)
