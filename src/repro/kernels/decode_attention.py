"""Flash-decode attention kernel (Pallas / TPU): one new token vs a KV cache.

Decode attention is HBM-bandwidth-bound (the whole KV cache is streamed for
a single query token), so the tunables differ from the prefill kernel —
this is precisely the paper's point that per-scenario tuning beats a single
hand-picked configuration:

    block_kv : KV rows streamed per grid step
    k_splits : partitions of the KV sequence processed by independent grid
               programs (flash-decoding); partial (acc, lse) results are
               combined in the wrapper. More splits ⇒ more parallelism for
               short batches, but more combine overhead.

GQA layout: all ``group = Hq // Hkv`` query heads that share one KV head are
processed together as the sublane dimension of a single tile, so each KV
block is read once per group instead of once per query head — the TPU
analogue of grouped-query flash-decoding.

Ragged batches (the paper's "variable lengths ... real-world online
inference") are supported via a per-batch ``kv_len`` operand that masks the
tail in-kernel.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref,     # inputs
                   o_ref, lse_ref,                    # outputs (partial)
                   acc_ref, m_ref, l_ref,             # scratch
                   *, scale: float, block_kv: int, blocks_per_split: int,
                   seq_kv: int, group: int):
    si = pl.program_id(1)          # which kv split
    bi = pl.program_id(2)          # block within split
    nb = pl.num_programs(2)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Clamp to the physical cache length: a caller may pass kv_len > T
    # (e.g. decode position past a full cache — "attend everything"), and
    # rows in [T, t_pad) are zero padding that must never score.
    kv_len = jnp.minimum(len_ref[0, 0], seq_kv)
    k_start = (si * blocks_per_split + bi) * block_kv
    run = k_start < kv_len

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (group, D)
        k = k_ref[0].astype(jnp.float32)            # (block_kv, D)
        v = v_ref[0].astype(jnp.float32)
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


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     kv_len: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     block_kv: int = 512, k_splits: int = 4,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B, Hq, D); k, v (B, Hkv, T, D); kv_len optional (B,) int32."""
    B, Hq, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if kv_len is None:
        kv_len = jnp.full((B,), T, jnp.int32)

    block_kv = min(block_kv, _round_up(T, 128))
    t_pad = _round_up(T, block_kv * k_splits)
    blocks_per_split = t_pad // (block_kv * k_splits)

    qg = q.reshape(B * Hkv, group, D)
    kp = _pad_axis(k, 2, t_pad).reshape(B * Hkv, t_pad, D)
    vp = _pad_axis(v, 2, t_pad).reshape(B * Hkv, t_pad, D)
    lens = jnp.broadcast_to(kv_len[:, None, None].astype(jnp.int32),
                            (B, Hkv, 1)).reshape(B * Hkv, 1)

    grid = (B * Hkv, k_splits, blocks_per_split)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_kv=block_kv,
        blocks_per_split=blocks_per_split, seq_kv=T, group=group)

    o_parts, lse_parts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, si, bi: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, group, D), lambda bh, si, bi: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (bh, si * nb + bi, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda bh, si, bi, nb=blocks_per_split:
                         (bh, si * nb + bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, D), lambda bh, si, bi: (bh, si, 0, 0)),
            pl.BlockSpec((1, 1, group, LANES),
                         lambda bh, si, bi: (bh, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, k_splits, group, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, k_splits, group, LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, D), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lens, qg, kp, vp)

    # ---- combine the k_splits partial results with logsumexp weights ------
    lse = lse_parts[..., 0]                             # (BHkv, S, group)
    m = jnp.max(lse, axis=1, keepdims=True)
    w = jnp.exp(lse - m)                                # (BHkv, S, group)
    o = jnp.sum(o_parts * w[..., None], axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1e-30)[..., None]
    return o.reshape(B, Hq, D).astype(q.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_axis(x: jnp.ndarray, axis: int, new: int) -> jnp.ndarray:
    if x.shape[axis] == new:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, new - x.shape[axis])
    return jnp.pad(x, pad)
