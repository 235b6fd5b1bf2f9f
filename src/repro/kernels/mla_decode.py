"""MLA (multi-head latent attention) decode kernel (Pallas / TPU).

DeepSeek-style MLA serving keeps the *compressed* KV cache — a rank-C latent
``ckv`` (B, T, C) shared by every query head plus a small decoupled RoPE key
``krope`` (B, T, R) — and decodes in the absorbed formulation: the per-head
up-projection W_uk is folded into the query, so attention runs directly
against the latent cache (the 93%-smaller-KV trick) and W_uv is applied to
the attended latent context afterwards.

In kernel terms decode-MLA is MQA with a wide head: one shared "KV head" of
width C (+R for scores), all Hq query heads packed as the sublane dimension
of a single tile. It is HBM-bound like GQA decode but with a very different
arithmetic shape (C ≈ 512 ≫ D ≈ 128), so its best block configuration does
not transfer from the GQA kernel — exactly the paper's argument for
per-kernel, per-scenario autotuning.

Tunables (see ``ops.mla_decode_space``):

    block_kv : latent-cache rows streamed per grid step
    k_splits : independent flash-decode partitions of the KV sequence;
               partial (acc, lse) pairs are combined in the wrapper

Ragged batches pass per-request ``kv_len``; blocks entirely past a
request's length are skipped (``pl.when``), tails are masked in-kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _pad_axis, _round_up
from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30
LANES = 128


def _mla_decode_kernel(len_ref, qa_ref, qr_ref, ckv_ref, kr_ref,  # inputs
                       o_ref, lse_ref,                            # outputs
                       acc_ref, m_ref, l_ref,                     # scratch
                       *, scale: float, block_kv: int,
                       blocks_per_split: int, seq_kv: int):
    bi = pl.program_id(2)          # block within this kv split
    nb = pl.num_programs(2)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Clamp to the physical cache length: kv_len > T means "attend all of
    # the cache"; rows in [T, t_pad) are zero padding and must never score.
    kv_len = jnp.minimum(len_ref[0, 0], seq_kv)
    k_start = (pl.program_id(1) * blocks_per_split + bi) * block_kv
    run = k_start < kv_len

    @pl.when(run)
    def _body():
        qa = qa_ref[0].astype(jnp.float32)           # (H, C)
        qr = qr_ref[0].astype(jnp.float32)           # (H, R)
        ckv = ckv_ref[0].astype(jnp.float32)         # (block_kv, C)
        kr = kr_ref[0].astype(jnp.float32)           # (block_kv, R)
        # Absorbed scores: q̃·ckvᵀ + q_rope·kropeᵀ   → (H, block_kv)
        s = jax.lax.dot_general(
            qa, ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s * scale
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # Attended latent context: p·ckv (the W_uv up-projection happens
        # outside the kernel, once per token, not per KV block).
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(bi == nb - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = acc_ref[...] / safe_l
        lse = jnp.where(l == 0.0, NEG_INF, m_ref[:, :1] + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def mla_decode(q_abs: jnp.ndarray, q_rope: jnp.ndarray, ckv: jnp.ndarray,
               krope: jnp.ndarray, *, kv_len: Optional[jnp.ndarray] = None,
               scale: Optional[float] = None, block_kv: int = 512,
               k_splits: int = 1,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Absorbed-MLA decode over the compressed cache.

    q_abs (B, H, C) — queries with W_uk absorbed; q_rope (B, H, R);
    ckv (B, T, C) latent cache; krope (B, T, R) decoupled RoPE keys;
    kv_len optional (B,) int32 valid lengths. Returns the attended latent
    context (B, H, C) in float32 — apply W_uv downstream.
    """
    B, H, C = q_abs.shape
    _, T, _ = ckv.shape
    R = q_rope.shape[-1]
    if scale is None:
        scale = 1.0
    if kv_len is None:
        kv_len = jnp.full((B,), T, jnp.int32)

    block_kv = min(block_kv, _round_up(T, 128))
    t_pad = _round_up(T, block_kv * k_splits)
    blocks_per_split = t_pad // (block_kv * k_splits)

    ckv_p = _pad_axis(ckv, 1, t_pad)
    kr_p = _pad_axis(krope, 1, t_pad)
    lens = kv_len.astype(jnp.int32).reshape(B, 1)

    grid = (B, k_splits, blocks_per_split)
    kernel = functools.partial(
        _mla_decode_kernel, scale=scale, block_kv=block_kv,
        blocks_per_split=blocks_per_split, seq_kv=T)

    o_parts, lse_parts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, si, bi: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, C), lambda b, si, bi: (b, 0, 0)),
            pl.BlockSpec((1, H, R), lambda b, si, bi: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, C),
                         lambda b, si, bi, nb=blocks_per_split:
                         (b, si * nb + bi, 0)),
            pl.BlockSpec((1, block_kv, R),
                         lambda b, si, bi, nb=blocks_per_split:
                         (b, si * nb + bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, C), lambda b, si, bi: (b, si, 0, 0)),
            pl.BlockSpec((1, 1, H, LANES), lambda b, si, bi: (b, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k_splits, H, C), jnp.float32),
            jax.ShapeDtypeStruct((B, k_splits, H, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, C), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lens, q_abs, q_rope, ckv_p, kr_p)

    # ---- combine the k_splits partial results with logsumexp weights ------
    lse = lse_parts[..., 0]                             # (B, S, H)
    m = jnp.max(lse, axis=1, keepdims=True)
    w = jnp.exp(lse - m)                                # (B, S, H)
    o = jnp.sum(o_parts * w[..., None], axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1e-30)[..., None]
    return o                                            # (B, H, C) float32
