"""Blocked MXU matmul kernel (Pallas / TPU).

Not an LLM-specific kernel, but the cleanest demonstration that the
autotuner's config spaces generalize (the paper's framing: the *method* is
the contribution, attention/RMS are the vehicles). Also used as the cost
anchor for MoE expert GEMMs.

Tunables: block_m, block_n, block_k — the canonical tiling triple. The
optimal triple shifts with MXU shape (128² on v4/v5, 256² on v6e) and VMEM
budget, which is exactly the cross-generation portability story.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), y_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jnp.ndarray, y: jnp.ndarray, *, block_m: int = 256,
           block_n: int = 256, block_k: int = 256,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (M, K) @ y (K, N) with fp32 accumulation."""
    M, K = x.shape
    K2, N = y.shape
    assert K == K2
    block_m = min(block_m, _round_up(M, 8))
    block_n = min(block_n, _round_up(N, 128))
    block_k = min(block_k, _round_up(K, 128))
    mp, kp, np_ = _round_up(M, block_m), _round_up(K, block_k), _round_up(N, block_n)
    xp = jnp.pad(x, ((0, mp - M), (0, kp - K))) if (mp, kp) != (M, K) else x
    yp = jnp.pad(y, ((0, kp - K), (0, np_ - N))) if (kp, np_) != (K, N) else y

    n_k = kp // block_k
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(mp // block_m, np_ // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xp, yp)
    return out[:M, :N]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
