"""Ragged batched GQA decode kernel (Pallas / TPU) — the serving hot path.

Online inference serves *ragged* batches: every request sits at a different
position in its KV cache, so a batch of B single-token queries attends to B
different valid lengths. This kernel streams each request's KV cache only up
to its own length (whole blocks past ``kv_len`` are skipped via ``pl.when``,
tails are masked in-kernel), so the HBM traffic — the thing decode is bound
by — tracks the *actual* tokens in the batch rather than the padded maximum.

It shares the flash-decode block structure with ``decode_attention`` (the
inner body is literally that kernel's) but exposes one more layout tunable:

    block_kv : KV rows streamed per grid step
    k_splits : independent KV partitions (flash-decoding); partials are
               combined in the wrapper
    pack_gqa : True  — all ``group = Hq // Hkv`` query heads sharing a KV
               head are processed together as the tile's sublane dim; each
               KV block is read once per group (minimal HBM traffic).
               False — one grid row per *query* head; the KV block is read
               ``group`` times but the parallel grid is ``group``× larger
               (wins for small batches on many-core chips).

The pack_gqa trade (bandwidth vs parallelism) flips with batch size, GQA
ratio, and chip — a per-scenario autotuning decision, not a constant.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _decode_kernel, _pad_axis, \
    _round_up
from repro.kernels.mode import resolve_interpret

LANES = 128


def gqa_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               kv_len: Optional[jnp.ndarray] = None,
               scale: Optional[float] = None,
               block_kv: int = 512, k_splits: int = 1,
               pack_gqa: bool = True,
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

    # Layout: pack_gqa folds each KV head's query group into the sublane dim
    # (rows = B*Hkv, tile (group, D)); unpacked gives every query head its
    # own grid row (rows = B*Hq, tile (1, D)) reading the shared KV block.
    g = group if pack_gqa else 1
    rows = B * Hkv if pack_gqa else B * Hq
    qg = q.reshape(rows, g, D)
    kp = _pad_axis(k, 2, t_pad).reshape(B * Hkv, t_pad, D)
    vp = _pad_axis(v, 2, t_pad).reshape(B * Hkv, t_pad, D)
    heads_per_b = Hkv if pack_gqa else Hq
    lens = jnp.broadcast_to(
        kv_len[:, None].astype(jnp.int32), (B, heads_per_b)).reshape(rows, 1)

    def kv_row(bh):
        return bh if pack_gqa else bh // group

    grid = (rows, k_splits, blocks_per_split)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_kv=block_kv,
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
    )(lens, qg, kp, vp)

    # ---- combine the k_splits partial results with logsumexp weights ------
    lse = lse_parts[..., 0]                             # (rows, S, g)
    m = jnp.max(lse, axis=1, keepdims=True)
    w = jnp.exp(lse - m)                                # (rows, S, g)
    o = jnp.sum(o_parts * w[..., None], axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1e-30)[..., None]
    return o.reshape(B, Hq, D).astype(q.dtype)
