"""Int8×int8 blocked MXU matmul with fused dequantization (Pallas / TPU).

The w8a8 GEMM: both operands arrive pre-quantized (per-channel or
per-tensor symmetric int8), the MXU accumulates int8×int8 → int32, and the
calibration scales are applied as part of the kernel instead of as
separate dequant passes. This is the kernel family where the paper's
"tuning spaces explode" observation bites hardest — on top of the tiling
triple, quantization adds two genuinely program-shaping tunables:

    block_m/n/k : the canonical tiling triple (as in ``matmul``), but the
                  optimal triple differs from the bf16 kernel's because
                  int8 operand tiles are half/quarter the bytes (more fits
                  in VMEM) while the int32 accumulator is full width.
    dequant     : "epilogue" — keep the exact int32 accumulator across the
                  K loop and apply scales once at the final store (minimal
                  VPU work; exact integer accumulation, safe for
                  K ≲ 130k).
                  "inline"   — convert each K-block's int32 partial to f32
                  *with scales applied* and accumulate in f32 (more VPU
                  work per step, but a float accumulator — the layout that
                  wins when the epilogue's int32 tile would thrash VMEM or
                  downstream fusion wants f32 partials).
    scale_gran  : "per_channel" — x scales (M, 1), w scales (1, N),
                  streamed as VMEM blocks alongside the operand tiles.
                  "per_tensor" — one scalar per operand, read from SMEM.
                  Granularity is a property of how the operands were
                  calibrated, so at runtime it is pinned by the operands
                  (the space constrains it via ``extra["scale_gran"]``,
                  exactly as ``paged_decode`` pins ``page_size`` to the
                  pool); offline deployment sweeps leave it free and the
                  winner tells the calibration pipeline what to emit.

Interpret-mode on this container; on TPU hosts the same grid runs on the
int8 MXU path (v5e: 394 TOPS int8 vs 197 TFLOPS bf16 — the 2× the cost
model sees through ``ChipSpec.flops_for_dtype``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _epilogue_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                     n_k: int, per_tensor: bool):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Exact integer accumulation on the MXU: int8 × int8 → int32.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == n_k - 1)
    def _store():
        if per_tensor:
            scale = xs_ref[0, 0] * ws_ref[0, 0]
            o_ref[...] = acc_ref[...].astype(jnp.float32) * scale
        else:
            o_ref[...] = (acc_ref[...].astype(jnp.float32)
                          * xs_ref[...] * ws_ref[...])


def _inline_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                   n_k: int, per_tensor: bool):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    # Dequantize the partial in place: f32 accumulator carries scaled values.
    if per_tensor:
        part = part * (xs_ref[0, 0] * ws_ref[0, 0])
    else:
        part = part * xs_ref[...] * ws_ref[...]
    acc_ref[...] += part

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


def matmul_w8a8(x: jnp.ndarray, w: jnp.ndarray, x_scale: jnp.ndarray,
                w_scale: jnp.ndarray, *, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                dequant: str = "epilogue", scale_gran: str = "per_channel",
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (M, K) int8 @ w (K, N) int8 → (M, N) float32, scales fused.

    ``x_scale`` is (M,)/(M, 1) per-row or scalar; ``w_scale`` is
    (N,)/(1, N) per-column or scalar — shapes must match ``scale_gran``.
    """
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8, (x.dtype, w.dtype)
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert dequant in ("epilogue", "inline"), dequant
    assert scale_gran in ("per_channel", "per_tensor"), scale_gran
    per_tensor = scale_gran == "per_tensor"
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1) if per_tensor \
        else jnp.asarray(x_scale, jnp.float32).reshape(M, 1)
    ws = jnp.asarray(w_scale, jnp.float32).reshape(1, 1) if per_tensor \
        else jnp.asarray(w_scale, jnp.float32).reshape(1, N)

    block_m = min(block_m, _round_up(M, 8))
    block_n = min(block_n, _round_up(N, 128))
    block_k = min(block_k, _round_up(K, 128))
    mp = _round_up(M, block_m)
    kp = _round_up(K, block_k)
    np_ = _round_up(N, block_n)
    xp = jnp.pad(x, ((0, mp - M), (0, kp - K))) if (mp, kp) != (M, K) else x
    wp = jnp.pad(w, ((0, kp - K), (0, np_ - N))) if (kp, np_) != (K, N) else w
    if not per_tensor:
        # Padded rows/cols scale by 0: their garbage never reaches [:M,:N].
        if mp != M:
            xs = jnp.pad(xs, ((0, mp - M), (0, 0)))
        if np_ != N:
            ws = jnp.pad(ws, ((0, 0), (0, np_ - N)))

    n_k = kp // block_k
    if per_tensor:
        scale_specs = [
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                         memory_space=pltpu.SMEM),
        ]
    else:
        scale_specs = [
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ]
    body = _epilogue_kernel if dequant == "epilogue" else _inline_kernel
    acc_dtype = jnp.int32 if dequant == "epilogue" else jnp.float32
    out = pl.pallas_call(
        functools.partial(body, n_k=n_k, per_tensor=per_tensor),
        grid=(mp // block_m, np_ // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ] + scale_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        interpret=resolve_interpret(interpret),
    )(xp, wp, xs, ws)
    return out[:M, :N]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
