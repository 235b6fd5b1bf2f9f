"""Pure-jnp oracles for every Pallas kernel in this package.

These are the "PyTorch native" equivalents from the paper's Table I: ~30 LoC
per kernel, obviously correct, used as the ground truth for the per-kernel
allclose sweeps in tests/ and as the numerics baseline in benchmarks.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: avoids NaNs on fully-masked rows


def _attn_mask(seq_q: int, seq_kv: int, *, causal: bool,
               window: Optional[int], q_offset: int,
               kv_len: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Boolean mask (…, seq_q, seq_kv); True = attend."""
    q_pos = jnp.arange(seq_q)[:, None] + q_offset
    k_pos = jnp.arange(seq_kv)[None, :]
    mask = jnp.ones((seq_q, seq_kv), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    if kv_len is not None:  # (B,) valid kv lengths (ragged batches)
        mask = mask[None] & (k_pos[None] < kv_len[:, None, None])
    return mask


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, q_offset: int = 0,
              kv_len: Optional[jnp.ndarray] = None,
              return_lse: bool = False):
    """Multi-head attention with GQA.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kq.astype(jnp.float32)) * scale
    mask = _attn_mask(Sq, k.shape[2], causal=causal, window=window,
                      q_offset=q_offset, kv_len=kv_len)
    if mask.ndim == 3:   # per-batch mask
        mask = mask[:, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / l, vq.astype(jnp.float32))
    o = o.astype(q.dtype)
    if return_lse:
        lse = (m + jnp.log(l))[..., 0]
        return o, lse
    return o


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     kv_len: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode: q (B, Hq, D); kv cache (B, Hkv, T, D)."""
    o = attention(q[:, :, None, :], k, v, causal=False, kv_len=kv_len,
                  scale=scale)
    return o[:, :, 0, :]


def gqa_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               kv_len: Optional[jnp.ndarray] = None,
               scale: Optional[float] = None) -> jnp.ndarray:
    """Ragged batched GQA decode: semantically identical to
    ``decode_attention`` — the kernel's pack_gqa/k_splits are pure layout."""
    return decode_attention(q, k, v, kv_len=kv_len, scale=scale)


def gather_pages(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Densify a paged KV pool: pages (Hkv, P, page_size, D) + block tables
    (B, max_pages) -> contiguous (B, Hkv, max_pages * page_size, D)."""
    Hkv, _, page_size, D = pages.shape
    B, n_blocks = block_tables.shape
    dense = pages[:, block_tables]            # (Hkv, B, n_blocks, ps, D)
    dense = jnp.moveaxis(dense, 1, 0)         # (B, Hkv, n_blocks, ps, D)
    return dense.reshape(B, Hkv, n_blocks * page_size, D)


def layer_pools(k_pages, v_pages, k_scales, v_scales, layer, head_dim):
    """One layer's pool ``(Hkv, P, page_size, head_dim)`` and scales
    ``(Hkv, P, page_size)``, from a single-layer pool or from every
    layer's stacked pool ``(L, Hkv, P, page_size, D)`` with scales
    ``(L, Hkv, P, 1, page_size)``; a pool's head dim may be padded past
    ``head_dim`` (``paged_decode.pool_head_dim``)."""
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scales is not None:
            k_scales, v_scales = (sc[layer][:, :, 0] for sc in (k_scales,
                                                              v_scales))
    return (k_pages[..., :head_dim], v_pages[..., :head_dim], k_scales,
            v_scales)


def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, kv_len: jnp.ndarray, *,
                 layer=0,
                 k_scales: Optional[jnp.ndarray] = None,
                 v_scales: Optional[jnp.ndarray] = None,
                 scale: Optional[float] = None) -> jnp.ndarray:
    """Paged decode oracle: gather each sequence's pages into a dense cache
    and run the dense ragged-decode reference. Rows with kv_len == 0
    (inactive batch slots) return zeros, matching the kernel. Int8 pools
    (the kv8 policy) pass per-token ``k_scales``/``v_scales``
    (Hkv, P, page_size) and are dequantized before the gather. A stacked
    pool is read at ``layer`` (see ``layer_pools``)."""
    k_pages, v_pages, k_scales, v_scales = layer_pools(
        k_pages, v_pages, k_scales, v_scales, layer, q.shape[-1])
    if k_scales is not None:
        k_pages = k_pages.astype(jnp.float32) * \
            k_scales.astype(jnp.float32)[..., None]
        v_pages = v_pages.astype(jnp.float32) * \
            v_scales.astype(jnp.float32)[..., None]
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    capacity = k.shape[2]
    lens = jnp.minimum(kv_len, capacity)
    o = decode_attention(q, k, v, kv_len=jnp.maximum(lens, 1), scale=scale)
    return jnp.where((lens > 0)[:, None, None], o, 0.0).astype(q.dtype)


def paged_verify(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, kv_len: jnp.ndarray, *,
                 layer=0,
                 k_scales: Optional[jnp.ndarray] = None,
                 v_scales: Optional[jnp.ndarray] = None,
                 scale: Optional[float] = None) -> jnp.ndarray:
    """Speculative-verify oracle: gather each sequence's pages dense, then
    score K consecutive query positions with a per-sequence causal tail.

    q (B, K, Hq, D); ``kv_len`` (B,) counts valid tokens *including* the K
    scattered draft positions, so query t (absolute position
    ``kv_len - K + t``) attends ``k_pos <= kv_len - K + t``. Query rows
    with an empty causal window (inactive slots, ``kv_len < K`` tails)
    return zeros, matching the kernel. A stacked pool is read at ``layer``.
    """
    k_pages, v_pages, k_scales, v_scales = layer_pools(
        k_pages, v_pages, k_scales, v_scales, layer, q.shape[-1])
    if k_scales is not None:
        k_pages = k_pages.astype(jnp.float32) * \
            k_scales.astype(jnp.float32)[..., None]
        v_pages = v_pages.astype(jnp.float32) * \
            v_scales.astype(jnp.float32)[..., None]
    B, K, Hq, D = q.shape
    k = gather_pages(k_pages, block_tables)     # (B, Hkv, T, D)
    v = gather_pages(v_pages, block_tables)
    Hkv, T = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    qh = jnp.moveaxis(q, 1, 2)                  # (B, Hq, K, D)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                   kq.astype(jnp.float32)) * scale
    lens = jnp.minimum(kv_len, T)
    q_pos = lens[:, None] - K + jnp.arange(K)[None, :]        # (B, K)
    mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]  # (B, K, T)
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / safe_l, vq.astype(jnp.float32))
    return jnp.moveaxis(o, 2, 1).astype(q.dtype)


def mla_decode(q_abs: jnp.ndarray, q_rope: jnp.ndarray, ckv: jnp.ndarray,
               krope: jnp.ndarray, *, kv_len: Optional[jnp.ndarray] = None,
               scale: float = 1.0) -> jnp.ndarray:
    """Absorbed-MLA decode oracle.

    q_abs (B, H, C) queries with W_uk absorbed; q_rope (B, H, R);
    ckv (B, T, C) latent cache; krope (B, T, R). Returns the attended
    latent context (B, H, C) float32 — W_uv applies downstream.
    """
    s = jnp.einsum("bhc,btc->bht", q_abs.astype(jnp.float32),
                   ckv.astype(jnp.float32))
    s = s + jnp.einsum("bhr,btr->bht", q_rope.astype(jnp.float32),
                       krope.astype(jnp.float32))
    s = s * scale
    if kv_len is not None:
        T = ckv.shape[1]
        s = jnp.where(jnp.arange(T)[None, None, :] < kv_len[:, None, None],
                      s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bht,btc->bhc", p, ckv.astype(jnp.float32))


def gqa_decode_kv8(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   k_scale: jnp.ndarray, v_scale: jnp.ndarray, *,
                   kv_len: Optional[jnp.ndarray] = None,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Int8-KV ragged decode oracle: dequantize the cache (per-token-per-
    head scales), then run the dense ragged reference. q (B, Hq, D) float;
    k, v (B, Hkv, T, D) int8; k_scale, v_scale (B, Hkv, T) f32."""
    kf = k.astype(jnp.float32) * k_scale.astype(jnp.float32)[..., None]
    vf = v.astype(jnp.float32) * v_scale.astype(jnp.float32)[..., None]
    return decode_attention(q, kf, vf, kv_len=kv_len, scale=scale)


def matmul_w8a8(x: jnp.ndarray, w: jnp.ndarray, x_scale: jnp.ndarray,
                w_scale: jnp.ndarray) -> jnp.ndarray:
    """w8a8 GEMM oracle: dequantize both int8 operands, matmul in f32.
    x (M, K) int8 with x_scale (M, 1) or scalar; w (K, N) int8 with
    w_scale (1, N) or scalar."""
    xs = jnp.asarray(x_scale, jnp.float32).reshape(-1, 1)
    ws = jnp.asarray(w_scale, jnp.float32).reshape(1, -1)
    xf = x.astype(jnp.float32) * xs
    wf = w.astype(jnp.float32) * ws
    return jnp.dot(xf, wf, preferred_element_type=jnp.float32)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    """RMS layer norm [Zhang & Sennrich 2019] over the last axis."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * (var + eps) ** -0.5
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def matmul(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(x.dtype)
