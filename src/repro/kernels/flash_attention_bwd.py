"""Flash-attention backward kernels (Pallas / TPU): dq and dk/dv.

Standard two-kernel recompute formulation (flash_attn v2):

  * ``dkv`` kernel — grid (B·Hkv, kv_blocks, G·q_blocks): for each KV tile,
    accumulate dk/dv over all query tiles *and all G grouped query heads*
    (GQA's dk/dv is the sum over the group — folding G into the innermost
    sequential axis keeps the accumulation in VMEM scratch).
  * ``dq`` kernel — grid (B·Hq, q_blocks, kv_blocks): accumulate dq over KV
    tiles.

Both recompute p = exp(s − lse) from the forward's logsumexp instead of
storing the S×T attention matrix — the O(S) memory property that makes
flash attention trainable at 32k context. ``delta = rowsum(do · o)`` is
computed in jnp (cheap elementwise) and streamed in.

Tunables mirror the forward (block_q, block_kv) but are tuned as a separate
TunableKernel ("flash_attention_bwd"): the optimal backward tiles differ —
the dkv kernel reads q/do per tile-pair, inverting the reuse pattern.
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


def _mask_and_run(qi, ki, *, block_q, block_kv, seq_q, seq_kv, causal,
                  window, q_offset):
    q_start = qi * block_q + q_offset
    k_start = ki * block_kv
    run = k_start <= jnp.minimum(q_start + block_q - 1, seq_kv - 1) \
        if causal else (k_start <= seq_kv - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_kv - 1 >=
                              q_start - (window - 1))
    return run


def _tile_mask(qi, ki, shape, *, block_q, block_kv, seq_q, seq_kv, causal,
               window, q_offset):
    q_pos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    m = jnp.logical_and(k_pos < seq_kv,
                        q_pos < seq_q + q_offset)
    if causal:
        m = jnp.logical_and(m, q_pos >= k_pos)
    if window is not None:
        m = jnp.logical_and(m, q_pos - k_pos < window)
    return m


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, window, block_q, block_kv,
                seq_q, seq_kv, q_offset, n_inner):
    ki = pl.program_id(1)
    inner = pl.program_id(2)          # g * n_q_blocks + qi

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    n_q = n_inner  # q blocks per head-group member
    qi = inner % n_q
    run = _mask_and_run(qi, ki, block_q=block_q, block_kv=block_kv,
                        seq_q=seq_q, seq_kv=seq_kv, causal=causal,
                        window=window, q_offset=q_offset)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        msk = _tile_mask(qi, ki, s.shape, block_q=block_q, block_kv=block_kv,
                         seq_q=seq_q, seq_kv=seq_kv, causal=causal,
                         window=window, q_offset=q_offset)
        p = jnp.where(msk, jnp.exp(s - lse), 0.0)        # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # p^T do
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # ds^T q

    @pl.when(inner == pl.num_programs(2) - 1)
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc,
               *, scale, causal, window, block_q, block_kv,
               seq_q, seq_kv, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = _mask_and_run(qi, ki, block_q=block_q, block_kv=block_kv,
                        seq_q=seq_q, seq_kv=seq_kv, causal=causal,
                        window=window, q_offset=q_offset)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        msk = _tile_mask(qi, ki, s.shape, block_q=block_q, block_kv=block_kv,
                         seq_q=seq_q, seq_kv=seq_kv, causal=causal,
                         window=window, q_offset=q_offset)
        p = jnp.where(msk, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _store():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _pad_to(x, axis, size):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True,
                        window: Optional[int] = None, scale=None,
                        q_offset: int = 0, block_q: int = 128,
                        block_kv: int = 128, interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gradients (dq, dk, dv). q/o/do (B,Hq,Sq,D); k,v (B,Hkv,Skv,D);
    lse (B,Hq,Sq)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    scale = scale or D ** -0.5
    block_q = min(block_q, -(-Sq // 8) * 8)
    block_kv = min(block_kv, -(-Skv // 128) * 128)
    sq_p = -(-Sq // block_q) * block_q
    skv_p = -(-Skv // block_kv) * block_kv
    n_q, n_k = sq_p // block_q, skv_p // block_kv

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                   # (B,Hq,Sq)
    qp = _pad_to(q, 2, sq_p).reshape(B * Hq, sq_p, D)
    dop = _pad_to(do, 2, sq_p).reshape(B * Hq, sq_p, D)
    kp = _pad_to(k, 2, skv_p).reshape(B * Hkv, skv_p, D)
    vp = _pad_to(v, 2, skv_p).reshape(B * Hkv, skv_p, D)
    # lse of padded rows must be huge so p = exp(s - lse) = 0.
    lsep = _pad_to(lse, 2, sq_p).reshape(B * Hq, sq_p)
    if sq_p != Sq:
        row = jnp.arange(sq_p)
        lsep = jnp.where(row[None, :] < Sq, lsep, 1e30)
    deltap = _pad_to(delta, 2, sq_p).reshape(B * Hq, sq_p, 1)
    lsep = lsep[..., None]

    lane_block = (1, block_q, 1)
    common = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_kv=block_kv, seq_q=Sq,
                  seq_kv=Skv, q_offset=q_offset)

    # --- dk/dv -------------------------------------------------------------
    def kvh(bh):
        return bh  # grid axis 0 is already B*Hkv

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_inner=n_q, **common),
        grid=(B * Hkv, n_k, G * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda bh, ki, inner, G=G, nq=n_q, hkv=Hkv, hq=Hq:
                         ((bh // hkv) * hq + (bh % hkv) * G + inner // nq,
                          inner % nq, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, ki, inner: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, ki, inner: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D),
                         lambda bh, ki, inner, G=G, nq=n_q, hkv=Hkv, hq=Hq:
                         ((bh // hkv) * hq + (bh % hkv) * G + inner // nq,
                          inner % nq, 0)),
            pl.BlockSpec(lane_block,
                         lambda bh, ki, inner, G=G, nq=n_q, hkv=Hkv, hq=Hq:
                         ((bh // hkv) * hq + (bh % hkv) * G + inner // nq,
                          inner % nq, 0)),
            pl.BlockSpec(lane_block,
                         lambda bh, ki, inner, G=G, nq=n_q, hkv=Hkv, hq=Hq:
                         ((bh // hkv) * hq + (bh % hkv) * G + inner // nq,
                          inner % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, D), lambda bh, ki, inner: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, ki, inner: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, skv_p, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, skv_p, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp, dop, lsep, deltap)

    # --- dq -----------------------------------------------------------------
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, qi, ki, G=G, hq=Hq, hkv=Hkv:
                         ((bh // hq) * hkv + (bh % hq) // G, ki, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, qi, ki, G=G, hq=Hq, hkv=Hkv:
                         ((bh // hq) * hkv + (bh % hq) // G, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec(lane_block, lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec(lane_block, lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp, dop, lsep, deltap)

    dq = dq.reshape(B, Hq, sq_p, D)[:, :, :Sq]
    dk = dk.reshape(B, Hkv, skv_p, D)[:, :, :Skv]
    dv = dv.reshape(B, Hkv, skv_p, D)[:, :, :Skv]
    return dq, dk, dv
