"""Ragged GQA decode over an int8 KV cache with in-kernel dequant
(Pallas / TPU) — the kv8 serving hot path.

Decode attention is HBM-bound: the whole KV cache streams past one query
token. Quantizing the cache to int8 halves-to-quarters that traffic (the
only term that matters), at the cost of a per-block dequant on the VPU —
the "dequant-in-kernel attention" pattern the Triton-attention anatomy
paper identifies as the spot where cross-platform tuning pays most. The
trade (smaller DMAs per block vs more VPU work per block) shifts the
optimal ``block_kv`` relative to the bf16 kernel, which is why this is a
separate registered kernel with its own tuning scenarios rather than a
flag on ``gqa_decode``.

Layout matches ``gqa_decode`` exactly (same grid, same partial-combine,
same tunables ``block_kv`` / ``k_splits`` / ``pack_gqa``) plus the scale
operands:

    k, v            (B, Hkv, T, D) int8
    k_scale, v_scale (B, Hkv, T) float32 — per-token-per-head symmetric
                    scales (written by the cache-append path: each token is
                    quantized once with its own absmax scale, so the cache
                    is self-calibrating — no offline calibration pass).

Dequant is positionally fused: scores use k_q·q scaled per column, the
value accumulation dequantizes v rows before the P·V contraction.
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


def _kv8_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,   # inputs
                o_ref, lse_ref,                                 # outputs
                acc_ref, m_ref, l_ref,                          # scratch
                *, scale: float, block_kv: int, blocks_per_split: int,
                seq_kv: int, group: int):
    si = pl.program_id(1)
    bi = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = jnp.minimum(len_ref[0, 0], seq_kv)
    k_start = (si * blocks_per_split + bi) * block_kv
    run = k_start < kv_len

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (group, D)
        # In-kernel dequant: int8 rows × per-token scales.
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][:, None]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (group, block_kv)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
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


def gqa_decode_kv8(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   k_scale: jnp.ndarray, v_scale: jnp.ndarray, *,
                   kv_len: Optional[jnp.ndarray] = None,
                   scale: Optional[float] = None,
                   block_kv: int = 512, k_splits: int = 1,
                   pack_gqa: bool = True,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B, Hq, D) float; k, v (B, Hkv, T, D) int8; k_scale, v_scale
    (B, Hkv, T) f32; kv_len optional (B,) int32."""
    B, Hq, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    assert k.dtype == jnp.int8 and v.dtype == jnp.int8, (k.dtype, v.dtype)
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if kv_len is None:
        kv_len = jnp.full((B,), T, jnp.int32)

    block_kv = min(block_kv, _round_up(T, 128))
    t_pad = _round_up(T, block_kv * k_splits)
    blocks_per_split = t_pad // (block_kv * k_splits)

    g = group if pack_gqa else 1
    rows = B * Hkv if pack_gqa else B * Hq
    qg = q.reshape(rows, g, D)
    kp = _pad_axis(k, 2, t_pad).reshape(B * Hkv, t_pad, D)
    vp = _pad_axis(v, 2, t_pad).reshape(B * Hkv, t_pad, D)
    # Padded tail scales are zero — dequantized pads contribute nothing
    # even before the positional mask.
    ksp = _pad_axis(k_scale.astype(jnp.float32), 2, t_pad).reshape(
        B * Hkv, t_pad)
    vsp = _pad_axis(v_scale.astype(jnp.float32), 2, t_pad).reshape(
        B * Hkv, t_pad)
    heads_per_b = Hkv if pack_gqa else Hq
    lens = jnp.broadcast_to(
        kv_len[:, None].astype(jnp.int32), (B, heads_per_b)).reshape(rows, 1)

    def kv_row(bh):
        return bh if pack_gqa else bh // group

    grid = (rows, k_splits, blocks_per_split)
    kernel = functools.partial(
        _kv8_kernel, scale=scale, block_kv=block_kv,
        blocks_per_split=blocks_per_split, seq_kv=T, group=g)

    o_parts, lse_parts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, si, bi: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, D), lambda bh, si, bi: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (kv_row(bh), si * nb + bi, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (kv_row(bh), si * nb + bi, 0)),
            pl.BlockSpec((1, block_kv),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (kv_row(bh), si * nb + bi)),
            pl.BlockSpec((1, block_kv),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (kv_row(bh), si * nb + bi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, D), lambda bh, si, bi: (bh, si, 0, 0)),
            pl.BlockSpec((1, 1, g, LANES),
                         lambda bh, si, bi: (bh, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, k_splits, g, D), jnp.float32),
            jax.ShapeDtypeStruct((rows, k_splits, g, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lens, qg, kp, vp, ksp, vsp)

    # ---- combine the k_splits partial results with logsumexp weights ------
    lse = lse_parts[..., 0]                             # (rows, S, g)
    m = jnp.max(lse, axis=1, keepdims=True)
    w = jnp.exp(lse - m)
    o = jnp.sum(o_parts * w[..., None], axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1e-30)[..., None]
    return o.reshape(B, Hq, D).astype(q.dtype)
