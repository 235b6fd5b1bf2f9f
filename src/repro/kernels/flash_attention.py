"""Causal flash-attention forward kernel (Pallas / TPU) with GQA + SWA.

TPU-native adaptation of the paper's Triton flash-attention kernel
(Table I, "Triton w/ autotuning"): one portable tile-level implementation
whose *configuration space* — not its code — adapts it to each chip
generation.

Tunables (the TPU analogue of Triton's BLOCK_M/BLOCK_N/num_warps/num_stages):
    block_q   : query-tile rows per grid step
    block_kv  : key/value-tile rows per grid step
  (occupancy knobs like num_warps have no TPU analogue — VMEM pressure via
   block shapes plays that role; see DESIGN.md §2.)

Grid: (batch × q_heads, Sq/block_q, Skv/block_kv); the kv axis is the
innermost, sequentialized ("arbitrary") axis, with the online-softmax state
(m, l, acc) carried in VMEM scratch across kv steps and the output block
written back once on the last step. Causal and sliding-window structure is
exploited by skipping fully-masked kv tiles with ``pl.when`` — block-level
sparsity, the same work-skipping flash_attn v2 does per CTA.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref,            # inputs
                  o_ref, lse_ref,                  # outputs
                  acc_ref, m_ref, l_ref,           # VMEM scratch
                  *, scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_kv: int, seq_q: int, seq_kv: int,
                  q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ---- block-level sparsity: is this kv tile visible at all? ------------
    q_start = qi * block_q + q_offset            # global position of q row 0
    q_end = q_start + block_q - 1
    k_start = ki * block_kv
    k_end = k_start + block_kv - 1
    run = k_start <= jnp.minimum(q_end, seq_kv - 1) if causal else \
        (k_start <= seq_kv - 1)
    if window is not None:
        run = jnp.logical_and(run, k_end >= q_start - (window - 1))

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)              # (block_q, D)
        k = k_ref[0].astype(jnp.float32)              # (block_kv, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_kv)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_kv                          # padded-tail bound
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                          # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (block_q, block_kv)
        alpha = jnp.exp(m_prev - m_new)                # rescale of history
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        # Fully-masked rows (padding) have l == 0: emit zeros, lse = -inf-ish.
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:]).astype(
            lse_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 256,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Flash attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D). GQA via Hq%Hkv==0.

    Sq/Skv need not divide the block sizes — inputs are zero-padded and the
    in-kernel bounds mask keeps padded keys invisible; padded query rows are
    sliced off the output.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    block_q = min(block_q, _round_up(Sq, 8))
    block_kv = min(block_kv, _round_up(Skv, 128))
    sq_pad = _round_up(Sq, block_q)
    skv_pad = _round_up(Skv, block_kv)
    qp = _pad_axis(q, 2, sq_pad).reshape(B * Hq, sq_pad, D)
    kp = _pad_axis(k, 2, skv_pad).reshape(B * Hkv, skv_pad, D)
    vp = _pad_axis(v, 2, skv_pad).reshape(B * Hkv, skv_pad, D)

    grid = (B * Hq, sq_pad // block_q, skv_pad // block_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, seq_q=Sq, seq_kv=Skv,
        q_offset=q_offset)

    out_shape = [
        jax.ShapeDtypeStruct((B * Hq, sq_pad, D), q.dtype),
        jax.ShapeDtypeStruct((B * Hq, sq_pad, LANES), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, qi, ki, g=group, hq=Hq, hkv=Hkv:
                         ((bh // hq) * hkv + (bh % hq) // g, ki, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, qi, ki, g=group, hq=Hq, hkv=Hkv:
                         ((bh // hq) * hkv + (bh % hq) // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)

    o = o.reshape(B, Hq, sq_pad, D)[:, :, :Sq]
    if return_lse:
        return o, lse.reshape(B, Hq, sq_pad, LANES)[:, :, :Sq, 0]
    return o


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_axis(x: jnp.ndarray, axis: int, new: int) -> jnp.ndarray:
    if x.shape[axis] == new:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, new - x.shape[axis])
    return jnp.pad(x, pad)
