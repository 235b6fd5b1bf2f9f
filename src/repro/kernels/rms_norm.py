"""RMS layer-norm kernel (Pallas / TPU) — the paper's second study kernel.

The vLLM CUDA original (``layernorm_kernels.cu``, 159 LoC) hand-assigns
thread blocks; the portable version simply tiles rows and lets the autotuner
pick the tile height per chip/shape:

    block_rows : rows normalized per grid step (VMEM pressure vs grid
                 overhead trade-off — the analogue of CUDA block dims)

Rows are processed at full feature width (one-pass sum-of-squares in fp32);
feature dims up to ~16k fit VMEM comfortably at the block heights in the
space, which the vmem_fits constraint enforces per chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import resolve_interpret


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float, n_rows: int,
                block_rows: int):
    xf = x_ref[...].astype(jnp.float32)                   # (block_rows, D)
    var = jnp.mean(xf * xf, axis=1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, *, eps: float = 1e-6,
             block_rows: int = 128,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (..., D) → RMS-normalized, scaled by weight (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    block_rows = min(block_rows, _round_up(N, 8))
    n_pad = _round_up(N, block_rows)
    if n_pad != N:
        x2 = jnp.pad(x2, ((0, n_pad - N), (0, 0)))

    kernel = functools.partial(_rms_kernel, eps=eps, n_rows=N,
                               block_rows=block_rows)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, D), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x2, weight.reshape(1, D))
    return out[:N].reshape(orig_shape)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
