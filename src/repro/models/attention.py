"""Attention module: GQA / MHA / MLA with selectable implementations.

Implementations (``impl=``):
  * "full"        — O(S·T) einsum + mask. Reference; smoke tests.
  * "chunked"     — lax.scan over KV chunks with online softmax and a
                    remat'd body: O(S) memory, XLA-native. This is the
                    structural path used by the 512-device dry-run and the
                    differentiable default for training (DESIGN.md §5).
  * "triangular"  — Python-unrolled query chunks attending to static causal
                    KV prefixes: removes the ~2× masked-tile waste of
                    "chunked" at the cost of a larger HLO. A §Perf
                    hillclimb lever.
  * "pallas"      — the autotuned flash-attention kernel (TPU production
                    path; interpret-mode here). Gradients via custom_vjp
                    with a chunked-recompute backward.

GQA is computed in grouped layout (B, Hkv, G, S, D) so KV is never
materialized per query head. MLA (DeepSeek) keeps the compressed KV cache
(c_kv ⊕ k_rope) and uses the absorbed formulation for decode.

Sliding-window (SWA) decode uses a ring-buffer KV cache of size ``window``
— the reason h2o-danube runs the long_500k cell with a 4k-slot cache.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distribution.sharding import shard, shard_heads_or_seq, tp_psum
from repro.models.config import ModelConfig
from repro.models.layers import rope
from repro.models.param import ParamSpec

Cache = Dict[str, jnp.ndarray]

# The page pool's reserved page (repro.serving.page_pool.SCRATCH_PAGE):
# never owned by a sequence, so writes with nothing to write land there.
SCRATCH_PAGE = 0


# ===========================================================================
# Core attention math (layout: q (B,S,Hq,Dq); k (B,T,Hkv,Dq); v (B,T,Hkv,Dv))
# ===========================================================================

def _group(q, n_kv):
    B, S, Hq, D = q.shape
    return q.reshape(B, S, n_kv, Hq // n_kv, D)


def _mask(sq, skv, *, causal, window, q_off, kv_off, kv_valid):
    q_pos = q_off + jnp.arange(sq)[:, None]
    k_pos = kv_off + jnp.arange(skv)[None, :]
    m = jnp.ones((sq, skv), bool)
    if causal:
        m &= q_pos >= k_pos
    if window is not None:
        m &= (q_pos - k_pos) < window
    if kv_valid is not None:
        m &= k_pos < kv_valid
    return m


def full_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                   kv_offset=0, kv_valid=None, scale=None):
    B, S, Hq, Dq = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    scale = scale or Dq ** -0.5
    qg = _group(q, Hkv)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    m = _mask(S, T, causal=causal, window=window, q_off=q_offset,
              kv_off=kv_offset, kv_valid=kv_valid)
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkv->bskgv", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, Hq, v.shape[-1]).astype(q.dtype)


def _bhsx(x):
    """Constrain a (B, H, S, X) attention activation consistently with the
    head-or-seq decision (keeps the online-softmax scan carry in ONE layout —
    otherwise the SPMD partitioner re-shards it every chunk iteration)."""
    from repro.distribution.sharding import shard_heads_or_seq
    return shard_heads_or_seq(x, head_axis=1, seq_axis=2)


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      chunk_kv=512, scale=None):
    B, S, Hq, Dq = q.shape
    T, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = Hq // Hkv
    scale = scale or Dq ** -0.5
    ck = min(chunk_kv, T)
    t_pad = -(-T // ck) * ck
    if t_pad != T:
        k = jnp.pad(k, ((0, 0), (0, t_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - T), (0, 0), (0, 0)))
    nT = t_pad // ck
    ks = jnp.moveaxis(k.reshape(B, nT, ck, Hkv, Dq), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nT, ck, Hkv, Dv), 1, 0)
    qh = _bhsx(jnp.moveaxis(q, 2, 1))                       # (B,Hq,S,Dq)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kj, vj, j = xs
        if G > 1:   # broadcast the KV *chunk* to all query heads (cheap)
            kj = jnp.repeat(kj, G, axis=2)
            vj = jnp.repeat(vj, G, axis=2)
        s = jnp.einsum("bhsd,bthd->bhst", qh, kj,
                       preferred_element_type=jnp.float32) * scale
        s = _bhsx(s)
        msk = _mask(S, ck, causal=causal, window=window, q_off=q_offset,
                    kv_off=j * ck, kv_valid=T)
        s = jnp.where(msk, s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhst,bthv->bhsv", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (_bhsx(m_new), _bhsx(l_new), _bhsx(acc)), None

    init = (
        _bhsx(jnp.full((B, Hq, S, 1), -1e30, jnp.float32)),
        _bhsx(jnp.zeros((B, Hq, S, 1), jnp.float32)),
        _bhsx(jnp.zeros((B, Hq, S, Dv), jnp.float32)),
    )
    (m_run, l_run, acc), _ = jax.lax.scan(
        jax.checkpoint(body), init, (ks, vs, jnp.arange(nT)))
    o = acc / jnp.maximum(l_run, 1e-30)
    o = jnp.moveaxis(o, 1, 2)                                # (B,S,Hq,Dv)
    return o.astype(q.dtype)


def triangular_attention(q, k, v, *, window=None, chunk_q=512, scale=None):
    """Causal self-attention with static per-q-chunk KV prefixes (no masked-
    tile waste). Requires Sq == T and q_offset == 0."""
    B, S, Hq, Dq = q.shape
    if S != k.shape[1] or S % min(chunk_q, S) != 0:
        return chunked_attention(q, k, v, causal=True, window=window,
                                 scale=scale)
    cq = min(chunk_q, S)
    outs = []
    for i in range(S // cq):
        hi = (i + 1) * cq
        lo = 0
        if window is not None:
            lo = max(0, (i * cq - window + 1) // cq * cq)
        outs.append(full_attention(
            q[:, i * cq:hi], k[:, lo:hi], v[:, lo:hi], causal=True,
            window=window, q_offset=i * cq, kv_offset=lo, scale=scale))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pallas_attention(q, k, v, causal, window, scale):
    from repro.kernels import ops as kops
    qt, kt, vt = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))
    o = kops.attention(qt, kt, vt, causal=causal, window=window)
    return jnp.moveaxis(o, 1, 2)


def _pallas_fwd(q, k, v, causal, window, scale):
    from repro.kernels import ops as kops
    qt, kt, vt = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))
    o, lse = kops.attention(qt, kt, vt, causal=causal, window=window,
                            return_lse=True)
    return jnp.moveaxis(o, 1, 2), (qt, kt, vt, o, lse)


def _pallas_bwd(causal, window, scale, res, g):
    """Pallas dq/dkv recompute kernels (flash_attention_bwd.py)."""
    from repro.kernels import ops as kops
    qt, kt, vt, o, lse = res
    do = jnp.moveaxis(g, 2, 1)
    dq, dk, dv = kops.attention_bwd(qt, kt, vt, o, lse, do, causal=causal,
                                    window=window)
    return tuple(jnp.moveaxis(x, 1, 2) for x in (dq, dk, dv))


_pallas_attention.defvjp(_pallas_fwd, _pallas_bwd)


def run_attention(q, k, v, *, impl="chunked", causal=True, window=None,
                  q_offset=0, chunk=512, scale=None):
    if impl == "full":
        return full_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, scale=scale)
    if impl == "triangular" and causal and q_offset == 0:
        return triangular_attention(q, k, v, window=window, chunk_q=chunk,
                                    scale=scale)
    if impl == "pallas":
        return _pallas_attention(q, k, v, causal, window, scale)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, chunk_kv=chunk, scale=scale)


# ===========================================================================
# Standard (GQA) attention layer
# ===========================================================================

def attn_specs(cfg: ModelConfig, cross: bool = False):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    if cfg.mla is not None and not cross:
        m = cfg.mla
        return {
            "wq": ParamSpec((d, hq * (m.qk_nope_dim + m.qk_rope_dim)),
                            ("d_model", "heads"), dt),
            "wdkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_dim),
                              ("d_model", None), dt),
            "kvnorm": ParamSpec((m.kv_lora_rank,), (None,), jnp.float32,
                                "ones"),
            "wuk": ParamSpec((hq, m.kv_lora_rank, m.qk_nope_dim),
                             ("heads", None, None), dt),
            "wuv": ParamSpec((hq, m.kv_lora_rank, m.v_head_dim),
                             ("heads", None, None), dt),
            "wo": ParamSpec((hq * m.v_head_dim, d), ("heads", "d_model"), dt),
        }
    specs = {
        "wq": ParamSpec((d, hq * dh), ("d_model", "heads"), dt),
        "wk": ParamSpec((d, hkv * dh), ("d_model", "kv_heads"), dt),
        "wv": ParamSpec((d, hkv * dh), ("d_model", "kv_heads"), dt),
        "wo": ParamSpec((hq * dh, d), ("heads", "d_model"), dt),
    }
    if cfg.norm == "layernorm":   # whisper-style biases
        specs["bq"] = ParamSpec((hq * dh,), ("heads",), jnp.float32, "zeros")
        specs["bv"] = ParamSpec((hkv * dh,), ("kv_heads",), jnp.float32,
                                "zeros")
        specs["bo"] = ParamSpec((d,), (None,), jnp.float32, "zeros")
    return specs


def _qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = shard_heads_or_seq(q.reshape(B, S, hq, dh), head_axis=2, seq_axis=1,
                           head_logical="heads")
    k = shard(k.reshape(B, S, hkv, dh), "batch", None, "kv_heads", None)
    v = shard(v.reshape(B, S, hkv, dh), "batch", None, "kv_heads", None)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _proj_out(p, o, cfg: ModelConfig):
    B, S = o.shape[:2]
    # Row-parallel under TP: each shard contracts its local heads against its
    # wo rows; the psum (no-op single-device) completes the sum BEFORE the
    # replicated bias so bo is not added tp× times.
    out = tp_psum(o.reshape(B, S, -1) @ p["wo"])
    if "bo" in p:
        out = out + p["bo"].astype(out.dtype)
    return shard(out, "batch", "seq", None)


def attn_forward(p, x, cfg: ModelConfig, *, impl="chunked", chunk=512,
                 causal=True, positions=None):
    """Training / no-cache forward."""
    if cfg.mla is not None:
        return _mla_forward(p, x, cfg, impl=impl, chunk=chunk,
                            positions=positions)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions)
    o = run_attention(q, k, v, impl=impl, causal=causal, window=cfg.window,
                      chunk=chunk)
    return _proj_out(p, o, cfg)


# --- caches ------------------------------------------------------------------

def _check_kv8(cfg: ModelConfig) -> None:
    if cfg.mla is not None:
        raise NotImplementedError(
            f"kv8 int8 caching needs the latent-cache quant path; "
            f"{cfg.name!r} uses MLA")


def _quant_kv_token(k, v):
    """Per-token-per-head symmetric int8 quantization of new KV entries
    (the cache is self-calibrating: every token carries its own absmax
    scale). Delegates to the shared kv8 wire-format contract so the
    runtime caches match the tuner's benchmark operands exactly."""
    from repro.quant.calibrate import quantize_kv
    return quantize_kv(k, v)


def attn_cache_spec(cfg: ModelConfig, batch: int, max_len: int,
                    kv_dtype: Optional[str] = None):
    """ShapeDtypeStructs of this layer's decode cache. ``kv_dtype="int8"``
    (the kv8 policy) stores int8 entries plus per-token-per-head f32
    scales in parallel ``k_scale``/``v_scale`` buffers."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        if kv_dtype is not None:
            _check_kv8(cfg)
        m = cfg.mla
        return {
            "ckv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dt),
            "krope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_dim), dt),
        }
    slots = min(max_len, cfg.window) if cfg.window else max_len
    shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
    if kv_dtype is None:
        return {"k": jax.ShapeDtypeStruct(shape, dt),
                "v": jax.ShapeDtypeStruct(shape, dt)}
    assert kv_dtype == "int8", kv_dtype
    sshape = (batch, slots, cfg.n_kv_heads)
    return {"k": jax.ShapeDtypeStruct(shape, jnp.int8),
            "v": jax.ShapeDtypeStruct(shape, jnp.int8),
            "k_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
            "v_scale": jax.ShapeDtypeStruct(sshape, jnp.float32)}


def attn_prefill(p, x, cfg: ModelConfig, *, max_len: int, impl="chunked",
                 chunk=512, kv_dtype: Optional[str] = None):
    """Forward over the prompt; returns (out, cache) with caches sized for
    ``max_len`` total positions (ring-buffered to ``window`` slots for
    SWA). ``kv_dtype="int8"`` stores the quantized kv8 cache (attention
    over the prompt itself still runs full precision — only what persists
    is quantized)."""
    if cfg.mla is not None:
        if kv_dtype is not None:
            _check_kv8(cfg)
        return _mla_prefill(p, x, cfg, max_len=max_len, impl=impl, chunk=chunk)
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions)
    o = run_attention(q, k, v, impl=impl, causal=True, window=cfg.window,
                      chunk=chunk)
    slots = min(max_len, cfg.window) if cfg.window else max_len
    srcs = {"k": k, "v": v}
    if kv_dtype is not None:
        assert kv_dtype == "int8", kv_dtype
        kq, ks, vq, vs = _quant_kv_token(k, v)
        srcs = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    if cfg.window and S > slots:
        idx = np.arange(S - slots, S)
        dst = idx % slots
    else:
        idx = np.arange(S)
        dst = idx % slots
    cache = {}
    for name, src in srcs.items():
        buf = jnp.zeros((B, slots) + src.shape[2:], src.dtype)
        buf = buf.at[:, dst].set(src[:, idx])
        axes = ("batch", None, "kv_heads") + (None,) * (buf.ndim - 3)
        cache[name] = shard(buf, *axes)
    return _proj_out(p, o, cfg), cache


def attn_decode(p, x, cfg: ModelConfig, cache: Cache, pos, *, impl="full"):
    """One-token decode. x (B, 1, d); pos scalar int32 (current index).

    ``impl="pallas"`` dispatches through the registry's ragged decode
    kernels (``gqa_decode_ragged`` / ``mla_decode``; ``gqa_decode_kv8``
    for int8 caches) with per-request valid lengths; sliding-window
    (ring-buffer) caches fall back to the einsum path because their slot
    order is not a contiguous KV prefix. A kv8 cache is detected by its
    ``k_scale`` buffer — the new token is quantized with its own absmax
    scale before the cache update.
    """
    if cfg.mla is not None:
        return _mla_decode(p, x, cfg, cache, pos, impl=impl)
    B = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    slots = cache["k"].shape[1]
    slot = pos % slots
    quantized = "k_scale" in cache         # kv8: int8 entries + scales
    if quantized:
        k, ks, v, vs = _quant_kv_token(k, v)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    new = {"k": ck, "v": cv}
    if quantized:
        new["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, slot, axis=1)
        new["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, slot, axis=1)

    if impl == "pallas" and cfg.window is None:
        from repro.kernels import ops as kops
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        if quantized:
            o = kops.ragged_decode_kv8(
                q[:, 0], jnp.moveaxis(ck, 1, 2), jnp.moveaxis(cv, 1, 2),
                jnp.moveaxis(new["k_scale"], 1, 2),
                jnp.moveaxis(new["v_scale"], 1, 2), kv_len=kv_len)
        else:
            o = kops.ragged_decode(q[:, 0], jnp.moveaxis(ck, 1, 2),
                                   jnp.moveaxis(cv, 1, 2), kv_len=kv_len)
        return _proj_out(p, o[:, None], cfg), new

    ckf, cvf = ck.astype(jnp.float32), cv.astype(jnp.float32)
    if quantized:                          # dequant for the einsum path
        ckf = ckf * new["k_scale"].astype(jnp.float32)[..., None]
        cvf = cvf * new["v_scale"].astype(jnp.float32)[..., None]
    qg = _group(q, hkv).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, ckf) * dh ** -0.5
    # Valid slots: s <= pos when the ring has not wrapped, else all.
    slot_ids = jnp.arange(slots)
    valid = jnp.logical_or(slot_ids <= pos, pos + 1 >= slots)
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkv->bskgv", prob, cvf)
    o = o.reshape(B, 1, hq, dh).astype(x.dtype)
    return _proj_out(p, o, cfg), new


# --- paged KV cache (continuous-batching serving, repro/serving/) ------------

def paged_cache_spec(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: Optional[str] = None):
    """ShapeDtypeStructs of one layer's shared page pool. Layout
    (Hkv, P, page_size, D): the paged_decode kernel's block-table index map
    picks (head, page) per grid step. D is the head dim rounded up to
    whole lanes (``pool_head_dim``), so that the device's default layout
    is the row-major one the kernels read in place. ``kv_dtype="int8"``
    (the kv8 policy) makes the pools int8 and adds parallel per-token
    scale pools (Hkv, P, 1, page_size) the kernel chases through the same
    tables (a (1, page_size) row per page is the block Mosaic can fetch).
    The model stacks every layer's pool of a unit along a leading axis
    (``lm.paged_cache_specs``)."""
    from repro.kernels.paged_decode import pool_head_dim
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.n_kv_heads, num_pages, page_size,
             pool_head_dim(cfg.head_dim))
    if kv_dtype is None:
        return {"k_pages": jax.ShapeDtypeStruct(shape, dt),
                "v_pages": jax.ShapeDtypeStruct(shape, dt)}
    assert kv_dtype == "int8", kv_dtype
    _check_kv8(cfg)
    sshape = (cfg.n_kv_heads, num_pages, 1, page_size)
    return {"k_pages": jax.ShapeDtypeStruct(shape, jnp.int8),
            "v_pages": jax.ShapeDtypeStruct(shape, jnp.int8),
            "k_scales": jax.ShapeDtypeStruct(sshape, jnp.float32),
            "v_scales": jax.ShapeDtypeStruct(sshape, jnp.float32)}


def _write_pages(pool, vals, block_tables, start, layer):
    """Write vals (B, S, Hkv, D) at token positions start[b] + s into layer
    ``layer`` of the stacked pool (L, Hkv, P, page_size, D'), whose head
    dim D' >= D is zero past D (``pool_head_dim``), through each
    sequence's block table (B, max_pages). Only ``dynamic_update_slice``
    writes the pool, in place: nothing the size of a pool is read, copied
    or relaid out. Per-token scales — (B, S, Hkv) into a scale pool
    (L, Hkv, P, 1, page_size) — take the same path with the slot axis
    last.

    A sequence's first page takes its n0 = min(S, page_size - slot)
    tokens from the start slot on, one write per binary digit of n0:
    fixed sizes that together cover exactly those slots, so the slots
    before the start keep their tokens. Each later page the chunk reaches
    takes one whole window from slot 0; its slots past the last token get
    zeros, harmless because they lie past the sequence's length (a later
    write fills them before attention reads them). A write with nothing
    to write (a zero digit, a page past the chunk or past the table) goes
    to the reserved scratch page, as must the caller's inactive rows
    (table entry 0)."""
    is_scale = vals.ndim == 3
    B, S = vals.shape[:2]
    page_size = pool.shape[-1] if is_scale else pool.shape[-2]
    slot_axis = 4 if is_scale else 3
    W = min(S, page_size)
    n_seg = (S + page_size - 2) // page_size + 1   # pages S tokens can touch
    max_block = block_tables.shape[1] - 1
    rows = jnp.moveaxis(vals, 2, 1).astype(pool.dtype)  # (B, Hkv, S[, D])
    pad = [(0, 0), (0, 0), (0, W)]              # W more rows past S
    if not is_scale:
        pad.append((0, pool.shape[-1] - rows.shape[-1]))
    rows = jnp.pad(rows, pad)

    def write(pool, b, first_token, size, page, slot):
        win = jax.lax.dynamic_slice_in_dim(rows[b], first_token, size, axis=1)
        shape = [1, win.shape[0], 1, 1, 1]
        shape[slot_axis] = size
        if not is_scale:
            shape[-1] = win.shape[-1]
        at = [layer, 0, page, 0, 0]
        at[slot_axis] = slot
        return jax.lax.dynamic_update_slice(pool, win.reshape(shape), at)

    for b in range(B):
        first, slot = start[b] // page_size, start[b] % page_size
        page = block_tables[b, jnp.minimum(first, max_block)]
        n0 = jnp.minimum(S, page_size - slot)
        off = 0
        for k in reversed(range(W.bit_length())):
            on = (n0 >> k) & 1
            pool = write(pool, b, off, 1 << k,
                         jnp.where(on == 1, page, SCRATCH_PAGE), slot + off)
            off = off + on * (1 << k)
        for i in range(1, n_seg):
            t0 = i * page_size - slot   # the chunk's first token on page i
            live = (t0 < S) & (first + i <= max_block)
            page = block_tables[b, jnp.minimum(first + i, max_block)]
            pool = write(pool, b, t0, W,
                         jnp.where(live, page, SCRATCH_PAGE), 0)
    return pool


def _gather_pages_bthd(pages, block_tables, layer, head_dim):
    """Densify layer ``layer`` of the stacked pool for the prefill path,
    reading only the tables' pages in place: (B, capacity, Hkv,
    head_dim)."""
    from repro.kernels.paged_gather import paged_gather
    dense = paged_gather(pages, block_tables, layer)   # (B, Hkv, nb, ps, D)
    B, Hkv, nb, ps, D = dense.shape
    dense = dense.reshape(B, Hkv, nb * ps, D)[..., :head_dim]
    return jnp.moveaxis(dense, 1, 2)


def _gather_scales_bth(scales, block_tables, layer):
    """Densify layer ``layer`` of a stacked per-token scale pool
    (L, Hkv, P, 1, page_size) through the block tables into
    (B, capacity, Hkv) — the scale-side twin of ``_gather_pages_bthd``."""
    from repro.kernels.paged_gather import paged_gather
    dense = paged_gather(scales, block_tables, layer)  # (B, Hkv, nb, 1, ps)
    B, Hkv, nb, _, ps = dense.shape
    return jnp.moveaxis(dense.reshape(B, Hkv, nb * ps), 1, 2)


def _write_kv(cache, k, v, block_tables, start, layer):
    """Quantize the new tokens' K/V when the pools are int8 (the kv8
    policy), write them into layer ``layer`` of the stacked pools, and
    return the new cache and the scale operands of the kernels."""
    new = dict(cache)
    scales = {}
    if "k_scales" in cache:                 # int8 pools (kv8 policy)
        k, ks, v, vs = _quant_kv_token(k, v)
        for name, val in (("k_scales", ks), ("v_scales", vs)):
            new[name] = _write_pages(cache[name], val, block_tables, start,
                                     layer)
        scales = {"k_scales": new["k_scales"], "v_scales": new["v_scales"]}
    new["k_pages"] = _write_pages(cache["k_pages"], k, block_tables, start,
                                  layer)
    new["v_pages"] = _write_pages(cache["v_pages"], v, block_tables, start,
                                  layer)
    return new, scales


def attn_prefill_paged(p, x, cfg: ModelConfig, cache, block_tables, start,
                       layer):
    """One chunked-prefill step: write the chunk's KV into layer ``layer``
    of the stacked pool, then attend the chunk's queries over the
    sequence's dense prefix (gathered through the block table) —
    q_offset=start, causal.

    x (B, S, d); block_tables (B, max_pages) int32; start (B,) int32 —
    tokens already resident per sequence (the chunk occupies
    [start, start+S)). Unused trailing slots must map to the scratch page.
    """
    assert cfg.mla is None and cfg.window is None, \
        "paged serving supports dense RoPE attention (no MLA/SWA yet)"
    B, S, _ = x.shape
    positions = start[:, None] + jnp.arange(S)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    new, scales = _write_kv(cache, k, v, block_tables, start, layer)
    kd = _gather_pages_bthd(new["k_pages"], block_tables, layer,
                            cfg.head_dim)
    vd = _gather_pages_bthd(new["v_pages"], block_tables, layer,
                            cfg.head_dim)
    if scales:
        # Dequantize AFTER the gather: scales ride the same block tables,
        # and only the pages the active sequences own get the f32 copy
        # (dequantizing the whole pool would transiently materialize a
        # 4×-pool-sized buffer — the memory the int8 pool exists to save).
        ksd = _gather_scales_bth(scales["k_scales"], block_tables, layer)
        vsd = _gather_scales_bth(scales["v_scales"], block_tables, layer)
        kd = kd.astype(jnp.float32) * ksd[..., None]
        vd = vd.astype(jnp.float32) * vsd[..., None]
    # Per-sequence q_offset differs: mask via kv_valid/causal per batch row.
    T = kd.shape[1]
    k_pos = jnp.arange(T)[None, None, :]                       # (1,1,T)
    valid = k_pos <= positions[:, :, None]                     # causal+resident
    qg = _group(q, cfg.n_kv_heads)
    scale = cfg.head_dim ** -0.5
    s = jnp.einsum("bskgd,btkd->bkgst", qg, kd,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkv->bskgv", prob.astype(vd.dtype), vd,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, S, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    return _proj_out(p, o, cfg), new


def attn_decode_paged(p, x, cfg: ModelConfig, cache, block_tables, lens,
                      layer):
    """One-token paged decode. x (B, 1, d); lens (B,) int32 tokens already
    resident (the new token lands at position lens[b]; rows with the
    scratch-only table and lens==0 are inactive padding slots). The token
    is written into, and attention reads, layer ``layer`` of the stacked
    pool in place.

    Dispatches the autotuned ``paged_decode`` registry kernel over the
    block tables — the serving hot path this subsystem exists for.
    """
    assert cfg.mla is None and cfg.window is None, \
        "paged serving supports dense RoPE attention (no MLA/SWA yet)"
    from repro.kernels import ops as kops
    positions = lens[:, None]                                  # (B, 1)
    q, k, v = _qkv(p, x, cfg, positions)
    new, scales = _write_kv(cache, k, v, block_tables, lens, layer)
    o = kops.paged_decode(q[:, 0], new["k_pages"], new["v_pages"],
                          block_tables, lens + 1, layer=layer, **scales)
    return _proj_out(p, o[:, None], cfg), new


def attn_verify_paged(p, x, cfg: ModelConfig, cache, block_tables, lens,
                      layer):
    """Speculative verify: score K consecutive positions in one pass.

    x (B, K, d) — the last committed token plus K-1 drafted continuations;
    lens (B,) int32 tokens already resident (the K inputs land at
    positions [lens, lens+K)). Writes all K positions' KV into layer
    ``layer`` of the stacked pool — rejected drafts leave stale entries
    past the accepted prefix, which is harmless: the scheduler rewinds
    ``pos`` and later writes overwrite.

    Dispatches the autotuned ``paged_verify`` registry kernel: query t
    attends the resident prefix plus drafts 0..t (kv_len = lens + K with
    in-kernel causal tails), so accepted outputs are exactly what K
    sequential ``attn_decode_paged`` calls would have produced.
    """
    assert cfg.mla is None and cfg.window is None, \
        "paged serving supports dense RoPE attention (no MLA/SWA yet)"
    from repro.kernels import ops as kops
    B, K, _ = x.shape
    positions = lens[:, None] + jnp.arange(K)[None, :]          # (B, K)
    q, k, v = _qkv(p, x, cfg, positions)
    new, scales = _write_kv(cache, k, v, block_tables, lens, layer)
    o = kops.paged_verify(q, new["k_pages"], new["v_pages"], block_tables,
                          lens + K, layer=layer, **scales)
    return _proj_out(p, o, cfg), new


# --- cross attention (whisper decoder) ----------------------------------------

def cross_specs(cfg: ModelConfig):
    return attn_specs(cfg, cross=True)


def cross_kv(p, enc, cfg: ModelConfig):
    B, T, _ = enc.shape
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc @ p["wk"]).reshape(B, T, hkv, dh)
    v = enc @ p["wv"]
    if "bv" in p:
        v = v + p["bv"].astype(v.dtype)
    return {"ck": k, "cv": v.reshape(B, T, hkv, dh)}


def cross_forward(p, x, cfg: ModelConfig, kv: Cache, *, impl="chunked",
                  chunk=512):
    B, S, _ = x.shape
    hq, dh = cfg.n_heads, cfg.head_dim
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, hq, dh)
    o = run_attention(q, kv["ck"], kv["cv"], impl=impl, causal=False,
                      chunk=chunk)
    return _proj_out(p, o, cfg)


# ===========================================================================
# MLA (DeepSeek multi-head latent attention)
# ===========================================================================

def _mla_qkv_rope_scale(cfg):
    m = cfg.mla
    return (m.qk_nope_dim + m.qk_rope_dim) ** -0.5


def _mla_project_q(p, x, cfg, positions):
    B, S, _ = x.shape
    m = cfg.mla
    hq = cfg.n_heads
    q = (x @ p["wq"]).reshape(B, S, hq, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_compress(p, x, cfg, positions):
    from repro.models.layers import apply_norm
    m = cfg.mla
    dkv = x @ p["wdkv"]
    ckv, krope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    xf = ckv.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    ckv = (xf * jax.lax.rsqrt(var + 1e-6) * p["kvnorm"]).astype(x.dtype)
    krope = rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, krope


def _mla_forward(p, x, cfg, *, impl="chunked", chunk=512, positions=None):
    B, S, _ = x.shape
    m = cfg.mla
    hq = cfg.n_heads
    if positions is None:
        positions = jnp.arange(S)
    q_nope, q_rope = _mla_project_q(p, x, cfg, positions)
    ckv, krope = _mla_compress(p, x, cfg, positions)
    # Decompress K/V per head (training form).
    k_nope = jnp.einsum("btc,hcn->bthn", ckv, p["wuk"].astype(x.dtype))
    v = jnp.einsum("btc,hcv->bthv", ckv, p["wuv"].astype(x.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                  (B, S, hq, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = run_attention(q, k, v, impl=impl, causal=True, chunk=chunk,
                      scale=_mla_qkv_rope_scale(cfg))
    return _proj_out(p, o, cfg)


def _mla_prefill(p, x, cfg, *, max_len, impl="chunked", chunk=512):
    B, S, _ = x.shape
    out = _mla_forward(p, x, cfg, impl=impl, chunk=chunk)
    positions = jnp.arange(S)
    ckv, krope = _mla_compress(p, x, cfg, positions)
    m = cfg.mla
    cc = jnp.zeros((B, max_len, m.kv_lora_rank), x.dtype).at[:, :S].set(ckv)
    cr = jnp.zeros((B, max_len, m.qk_rope_dim), x.dtype).at[:, :S].set(krope)
    return out, {"ckv": shard(cc, "batch", None, None),
                 "krope": shard(cr, "batch", None, None)}


def _mla_decode(p, x, cfg, cache: Cache, pos, *, impl="full"):
    """Absorbed-MLA decode over the compressed cache (the 93%-smaller-KV
    trick that makes deepseek-v2 serving cheap). ``impl="pallas"`` runs the
    score/softmax/context loop in the autotuned ``mla_decode`` kernel."""
    B = x.shape[0]
    m = cfg.mla
    hq = cfg.n_heads
    positions = jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope = _mla_project_q(p, x, cfg, positions)
    ckv_t, krope_t = _mla_compress(p, x, cfg, positions)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_t, pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope_t, pos,
                                                axis=1)
    # Absorb W_uk into the query: q̃ (B,1,H,C)
    q_abs = jnp.einsum("bshn,hcn->bshc", q_nope, p["wuk"].astype(x.dtype))
    if impl == "pallas":
        from repro.kernels import ops as kops
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        ctx_lat = kops.latent_decode(q_abs[:, 0], q_rope[:, 0], ckv, krope,
                                  kv_len=kv_len,
                                  scale=_mla_qkv_rope_scale(cfg))
        o = jnp.einsum("bhc,hcv->bhv", ctx_lat,
                       p["wuv"].astype(jnp.float32))[:, None].astype(x.dtype)
        return _proj_out(p, o, cfg), {"ckv": ckv, "krope": krope}
    s = jnp.einsum("bshc,btc->bhst", q_abs.astype(jnp.float32),
                   ckv.astype(jnp.float32))
    s = s + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                       krope.astype(jnp.float32))
    s = s * _mla_qkv_rope_scale(cfg)
    T = ckv.shape[1]
    valid = jnp.arange(T) <= pos
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btc->bshc", prob, ckv.astype(jnp.float32))
    o = jnp.einsum("bshc,hcv->bshv", ctx,
                   p["wuv"].astype(jnp.float32)).astype(x.dtype)
    return _proj_out(p, o, cfg), {"ckv": ckv, "krope": krope}
