"""Autotuned public kernel API — JIT autotuning at the call site.

This module is the integration point between the kernels and the paper's
autotuner: for each kernel it declares

  * a ``ConfigSpace`` with platform-conditional validity constraints (Q4.1),
  * a ``workload_fn`` (config → KernelWorkload) for analytical TPU tuning,
  * a ``make_runner`` factory for wall-clock tuning (interpret-mode on this
    container, real kernels on a TPU host),
  * a ``heuristic`` — the untuned "pick something reasonable" default that
    plays the role of the paper's vendor/template baseline configuration,

and then **registers** the kernel in ``repro.kernels.registry`` together
with its scenario tags (prefill / decode / gqa / mla / ...), its ``ref.py``
oracle, its public entry point, and canonical benchmark cases. The registry
is the single enumeration point — the tuner, benchmarks, serving launcher,
and model layers all discover kernels through it (see DESIGN.md §1);
nothing else keeps a kernel list.

Public entry points (``attention``, ``decode``, ``ragged_decode``,
``ragged_decode_kv8``, ``paged_decode``, ``latent_decode``, ``rmsnorm``,
``matmul``, ``matmul_w8a8``; entry names differ from their
kernel-body module names so the package namespace never collides) look up the best known config from
the process tuner (persistent-cache hit, JIT tune, or heuristic +
background enqueue, per policy) and dispatch. Every entry point accepts
``config=`` to bypass tuning (used by benchmarks that sweep configs
explicitly, reproducing the paper's Fig. 4/5 analyses).
"""

from __future__ import annotations

import collections
import functools
import logging
import math
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    Autotuner, Config, ConfigSpace, KernelRunner, KernelWorkload,
    MatmulShape, Param, TunableKernel, TuningContext, default_tuner,
)
from repro.core.config_space import dtype_bytes, vmem_fits
from repro.obs import drift as drift_lib

LANES = 128

log = logging.getLogger("repro.ops")


# ---------------------------------------------------------------------------
# Serve-time kernel guard (fault tolerance; docs/serving.md).
#
# A tuned config that measured fine can still fail in production — raise at
# trace/compile time, or return non-finite output. The guard wraps the
# tuner-dispatch path of serving-critical entry points: a failing config is
# quarantined in the tuning cache (Autotuner.quarantine, which also enqueues
# a background re-tune), the dispatch falls back to the next-best runner-up
# from the winning search, then the attached config portfolio's members for
# the scenario (core/portfolio.py — already validity-checked, excluding the
# quarantined config), then the heuristic default, and as a last resort the
# ref.py oracle impl — the engine degrades instead of going down.
#
# Active when a FaultPlan is installed (serving/faults.py) or under
# REPRO_KERNEL_GUARD=1; off by default so unit tests exercising kernels
# directly surface real bugs instead of silently passing through the oracle.
# ---------------------------------------------------------------------------

def _guard_active() -> bool:
    from repro.serving import faults as fault_lib
    return (fault_lib.get_active() is not None
            or os.environ.get("REPRO_KERNEL_GUARD", "0") == "1")


def _guarded_dispatch(kernel: TunableKernel, ctx: Optional[TuningContext],
                      config: Config, run: Callable[[Config], Any],
                      ref_run: Callable[[], Any],
                      tuner: Optional[Autotuner]):
    """Run ``run(config)`` with quarantine-and-fallback semantics; consult
    the active FaultPlan for injected dispatch faults. Under jit this
    executes at trace time — exactly where a hostile config's exceptions
    surface; the eager non-finite check only fires on concrete outputs
    (the jitted serving path is covered by the engine's logits guard)."""
    from repro.serving import faults as fault_lib
    plan = fault_lib.get_active()

    def attempt(cfg):
        kind = plan.take_dispatch(kernel.name) if plan is not None else None
        if kind == "kernel_exception":
            raise fault_lib.InjectedKernelError(
                f"injected kernel failure in {kernel.name}")
        if kind == "compile_failure":
            raise fault_lib.InjectedCompileError(
                f"injected compile failure in {kernel.name}")
        out = run(cfg)
        if kind == "nan_output" and jnp.issubdtype(out.dtype, jnp.floating):
            out = out * jnp.asarray(float("nan"), out.dtype)
        return out

    def quarantine(cfg):
        if tuner is not None and ctx is not None:
            tuner.quarantine(kernel, ctx, cfg)

    candidates = [config]
    if tuner is not None and ctx is not None:
        candidates += tuner.fallback_configs(kernel, ctx, exclude=[config])
    for cfg in candidates:
        try:
            out = attempt(cfg)
        except Exception as e:       # noqa: BLE001 — degrade, don't die
            quarantine(cfg)
            log.warning("%s raised under config %s (%s); falling back",
                        kernel.name, cfg, e)
            continue
        if (not isinstance(out, jax.core.Tracer)
                and jnp.issubdtype(out.dtype, jnp.floating)
                and not bool(jnp.isfinite(out).all())):
            quarantine(cfg)
            log.warning("%s returned non-finite output under config %s; "
                        "falling back", kernel.name, cfg)
            continue
        return out
    log.warning("%s: every tuned config failed; serving the reference "
                "oracle impl (degraded mode)", kernel.name)
    return ref_run()


def _timed_dispatch(kernel: TunableKernel, ctx: Optional[TuningContext],
                    config: Config, tuner: Optional[Autotuner],
                    run: Callable[[Config], Any]):
    """Tuner-path dispatch with drift sampling (obs/drift.py): when a
    drift detector is active and the call is eager (concrete output —
    interpret-mode kernels, tests, benchmarks), time the launch and feed
    the sample under the tuning-cache key. Under jit the output is a
    tracer and per-launch timing is meaningless — the serving engine
    times whole jitted steps and attributes them via ``last_dispatch``
    instead. Either way ``dispatch_key`` registers the key in the
    tuner's key index, which is what lets ``retune_key`` map a flagged
    drift key back to its (kernel, ctx) scenario for online retuning."""
    det = drift_lib.get_active()
    if det is None or ctx is None or tuner is None:
        return run(config)
    t0 = time.perf_counter()
    out = run(config)
    if isinstance(out, jax.core.Tracer):
        return out
    jax.block_until_ready(out)
    key, shipped = tuner.dispatch_key(kernel, ctx)
    det.observe(key, time.perf_counter() - t0, shipped=shipped,
                kernel=kernel.name)
    return out


def _ctx(tuner: Autotuner, shapes: Dict[str, Tuple[int, ...]], dtype: str,
         **extra) -> TuningContext:
    chip = getattr(tuner.backend, "chip", None)
    if chip is None:
        chip = getattr(getattr(tuner.backend, "analytical", None), "chip", None)
    if chip is None:
        from repro.core.hardware import default_chip
        chip = default_chip()
    # Inside a tensor_parallel shard_map body the entry points trace with
    # per-shard LOCAL shapes; stamping the mesh signature keeps those tuning
    # scenarios (and their cached winners) distinct from an unsharded model
    # with the same shapes (DESIGN.md §11). Unsharded runs sign mesh={}.
    from repro.distribution.sharding import current_mesh_signature
    return TuningContext(chip=chip, shapes=shapes, dtype=dtype, extra=extra,
                         mesh=current_mesh_signature())


# Runner factories are called once per candidate config, but the operands
# they build depend only on (key, shape, dtype) — memoize them so tuning a
# 70-config space doesn't regenerate the same arrays 70 times. Bounded LRU:
# operands for host-scale bench cases are small, but don't pin arbitrarily
# many of them alive.
_OPERAND_MEMO: "collections.OrderedDict[Tuple, Any]" = collections.OrderedDict()
_OPERAND_MEMO_LOCK = threading.Lock()
_OPERAND_MEMO_MAX = 64


def _memo_operand(cache_key, build):
    with _OPERAND_MEMO_LOCK:
        if cache_key in _OPERAND_MEMO:
            _OPERAND_MEMO.move_to_end(cache_key)
            return _OPERAND_MEMO[cache_key]
    out = build()
    with _OPERAND_MEMO_LOCK:
        _OPERAND_MEMO[cache_key] = out
        while len(_OPERAND_MEMO) > _OPERAND_MEMO_MAX:
            _OPERAND_MEMO.popitem(last=False)
    return out


def _rand(key, shape, dtype):
    k = ("normal", tuple(jax.device_get(key).tolist()), tuple(shape),
         str(dtype))
    return _memo_operand(
        k, lambda: jax.random.normal(key, shape, jnp.float32).astype(dtype))


# ===========================================================================
# Flash attention (prefill / training forward)
# ===========================================================================

def _flash_vmem(cfg: Config, ctx: TuningContext) -> int:
    D = ctx.shape("q")[3]
    if cfg.get("pad_head_dim"):
        D = -(-D // LANES) * LANES
    ib = dtype_bytes(ctx.dtype)
    bq, bk = cfg["block_q"], cfg["block_kv"]
    buf = 2 * (bq * D * ib + 2 * bk * D * ib + bq * D * ib + bq * LANES * 4)
    scratch = bq * D * 4 + 2 * bq * LANES * 4
    return buf + scratch


def flash_attention_space() -> ConfigSpace:
    sp = ConfigSpace(
        "flash_attention",
        [
            Param("block_q", (64, 128, 256, 512, 1024, 2048)),
            Param("block_kv", (128, 256, 512, 1024, 2048, 4096)),
            Param("pad_head_dim", (False, True)),
        ],
        version=2,
    )
    sp.constrain("vmem", vmem_fits(_flash_vmem))
    sp.constrain("block_q<=seq_q",
                 lambda c, x: c["block_q"] <= max(64, _rup(x.shape("q")[2], 8)))
    sp.constrain("block_kv<=seq_kv",
                 lambda c, x: c["block_kv"] <= max(128, _rup(x.shape("k")[2], 128)))
    return sp


def _flash_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, Sq, D = ctx.shape("q")
    _, Hkv, Skv, _ = ctx.shape("k")
    causal = bool(ctx.extra.get("causal", True))
    window = ctx.extra.get("window") or None
    Dp = -(-D // LANES) * LANES if cfg["pad_head_dim"] else D
    ib = dtype_bytes(ctx.dtype)
    bq, bk = min(cfg["block_q"], _rup(Sq, 8)), min(cfg["block_kv"], _rup(Skv, 128))
    nq, nk = _cdiv(Sq, bq), _cdiv(Skv, bk)

    # Fraction of (q-block, kv-block) tiles actually executed.
    if window is not None and causal:
        vis = min(1.0, (window + bq + bk) / max(Skv, 1))
    elif causal:
        vis = min(1.0, (0.5 * Skv + bq) / max(Skv, 1))
    else:
        vis = 1.0
    run_steps = B * Hq * nq * max(1, int(round(nk * vis)))

    flops = 4.0 * B * Hq * Sq * Skv * D * vis          # qk^T + pv
    vflops = 6.0 * B * Hq * Sq * Skv * vis             # softmax pipeline
    bytes_q = B * Hq * Sq * Dp * ib
    bytes_kv = 2.0 * run_steps * bk * Dp * ib          # kv streamed per tile
    bytes_o = B * Hq * Sq * (Dp * ib + 4 * LANES)
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_q + bytes_kv + bytes_o,
        grid_steps=B * Hq * nq * nk,
        vmem_bytes=_flash_vmem(cfg, ctx),
        matmuls=[MatmulShape(bq, Dp, bk), MatmulShape(bq, bk, Dp)],
        vector_flops=vflops,
        dtype=ctx.dtype,
        parallel_grid=B * Hq * nq,
    )


def _flash_heuristic(ctx: TuningContext) -> Config:
    # "What a sensible developer hard-codes": the flash_attn-v2 default tile.
    return {"block_q": 128, "block_kv": 128, "pad_head_dim": False}


def _flash_canonical(cfg: Config, ctx: TuningContext) -> Config:
    # pad_head_dim is a no-op when the head dim is already lane-aligned —
    # both variants lower to the identical program.
    c = dict(cfg)
    if ctx.shape("q")[3] % LANES == 0:
        c["pad_head_dim"] = False
    return c


def _flash_runner(cfg: Config, ctx: TuningContext):
    q_s, k_s = ctx.shape("q"), ctx.shape("k")
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], q_s, dtype)
    k = _rand(keys[1], k_s, dtype)
    v = _rand(keys[2], k_s, dtype)
    fn = jax.jit(functools.partial(
        _flash_dispatch, causal=bool(ctx.extra.get("causal", True)),
        window=ctx.extra.get("window") or None, config=dict(cfg)))
    return KernelRunner(fn, q, k, v)


def _flash_dispatch(q, k, v, *, causal, window, config, q_offset=0,
                    interpret=None, return_lse=False):
    from repro.kernels.flash_attention import flash_attention
    D = q.shape[-1]
    cfg = dict(config)
    if cfg.pop("pad_head_dim", False) and D % LANES:
        Dp = -(-D // LANES) * LANES
        pad = [(0, 0)] * 3 + [(0, Dp - D)]
        scale = D ** -0.5
        out = flash_attention(jnp.pad(q, pad), jnp.pad(k, pad),
                              jnp.pad(v, pad), causal=causal, window=window,
                              scale=scale, q_offset=q_offset,
                              interpret=interpret, return_lse=return_lse,
                              **cfg)
        if return_lse:
            return out[0][..., :D], out[1]
        return out[..., :D]
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, interpret=interpret,
                           return_lse=return_lse, **cfg)


FLASH_ATTENTION = TunableKernel(
    name="flash_attention",
    space=flash_attention_space(),
    version=2,
    workload_fn=_flash_workload,
    make_runner=_flash_runner,
    heuristic=_flash_heuristic,
    canonicalize=_flash_canonical,
)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              q_offset: int = 0, config: Optional[Config] = None,
              tuner: Optional[Autotuner] = None,
              interpret: Optional[bool] = None,
              return_lse: bool = False):
    """Autotuned flash attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D)."""
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q": q.shape, "k": k.shape}, str(q.dtype),
                   causal=causal, window=window or 0)
        config = tuner.best_config(FLASH_ATTENTION, ctx)
    return _flash_dispatch(q, k, v, causal=causal, window=window,
                           config=config, q_offset=q_offset,
                           interpret=interpret, return_lse=return_lse)


# ===========================================================================
# Flash attention backward (training)
# ===========================================================================

def _flash_bwd_vmem(cfg: Config, ctx: TuningContext) -> int:
    D = ctx.shape("q")[3]
    ib = dtype_bytes(ctx.dtype)
    bq, bk = cfg["block_q"], cfg["block_kv"]
    # q, k, v, do tiles (×2 double-buffered) + dk/dv f32 scratch + lse/delta
    buf = 2 * (2 * bq * D * ib + 2 * bk * D * ib + 2 * bq * 4)
    scratch = 2 * bk * D * 4 + bq * D * 4
    return buf + scratch


def flash_attention_bwd_space() -> ConfigSpace:
    sp = ConfigSpace(
        "flash_attention_bwd",
        [
            Param("block_q", (64, 128, 256, 512)),
            Param("block_kv", (128, 256, 512, 1024)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_flash_bwd_vmem))
    return sp


def _flash_bwd_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, Sq, D = ctx.shape("q")
    _, Hkv, Skv, _ = ctx.shape("k")
    causal = bool(ctx.extra.get("causal", True))
    vis = 0.5 if causal else 1.0
    ib = dtype_bytes(ctx.dtype)
    bq, bk = min(cfg["block_q"], _rup(Sq, 8)), min(cfg["block_kv"],
                                                   _rup(Skv, 128))
    nq, nk = _cdiv(Sq, bq), _cdiv(Skv, bk)
    # dkv: 4 matmuls/tile; dq: 3 matmuls/tile (s recompute shared notionally)
    flops = 14.0 * B * Hq * Sq * Skv * D * vis
    tiles = B * Hq * nq * nk * vis
    bytes_ = tiles * (2 * bq * D + 2 * bk * D) * ib * 2 +         B * Hq * Sq * D * ib * 3
    return KernelWorkload(
        flops=flops, hbm_bytes=bytes_,
        grid_steps=int(B * Hkv * nk * (Hq // Hkv) * nq + B * Hq * nq * nk),
        vmem_bytes=_flash_bwd_vmem(cfg, ctx),
        matmuls=[MatmulShape(bq, D, bk), MatmulShape(bk, bq, D)],
        vector_flops=8.0 * B * Hq * Sq * Skv * vis,
        dtype=ctx.dtype,
        parallel_grid=B * Hkv * nk,
    )


FLASH_ATTENTION_BWD = TunableKernel(
    name="flash_attention_bwd",
    space=flash_attention_bwd_space(),
    version=1,
    workload_fn=_flash_bwd_workload,
    heuristic=lambda ctx: {"block_q": 128, "block_kv": 128},
)


def attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                  config: Optional[Config] = None,
                  tuner: Optional[Autotuner] = None,
                  interpret: Optional[bool] = None):
    """Autotuned flash-attention gradients (dq, dk, dv). Layout (B,H,S,D)."""
    from repro.kernels.flash_attention_bwd import flash_attention_bwd
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q": q.shape, "k": k.shape}, str(q.dtype),
                   causal=causal, window=window or 0)
        config = tuner.best_config(FLASH_ATTENTION_BWD, ctx)
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, interpret=interpret, **config)


# ===========================================================================
# Decode attention (single token vs KV cache)
# ===========================================================================

def _decode_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, Hq, D = ctx.shape("q")
    Hkv = ctx.shape("k")[1]
    group = max(1, Hq // Hkv)
    ib = dtype_bytes(ctx.dtype)
    bk = cfg["block_kv"]
    buf = 2 * (2 * bk * D * ib + group * D * ib)
    scratch = group * D * 4 + 2 * group * LANES * 4
    out = 2 * (group * D * 4 + group * LANES * 4)
    return buf + scratch + out


def decode_attention_space() -> ConfigSpace:
    sp = ConfigSpace(
        "decode_attention",
        [
            Param("block_kv", (128, 256, 512, 1024, 2048)),
            Param("k_splits", (1, 2, 4, 8, 16, 32)),
        ],
        version=2,
    )
    sp.constrain("vmem", vmem_fits(_decode_vmem))
    sp.constrain(
        "splits<=blocks",
        lambda c, x: c["k_splits"] <= max(1, _cdiv(x.shape("k")[2],
                                                   c["block_kv"])))
    return sp


def _decode_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    group = max(1, Hq // Hkv)
    ib = dtype_bytes(ctx.dtype)
    bk = min(cfg["block_kv"], _rup(T, 128))
    ks = cfg["k_splits"]
    t_pad = _rup(T, bk * ks)
    blocks = t_pad // bk
    flops = 4.0 * B * Hq * T * D
    bytes_kv = 2.0 * B * Hkv * t_pad * D * ib
    bytes_q = B * Hkv * ks * group * D * ib
    bytes_part = 2.0 * B * Hkv * ks * group * (D + LANES) * 4  # write+combine
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_part,
        grid_steps=B * Hkv * blocks,
        vmem_bytes=_decode_vmem(cfg, ctx),
        matmuls=[MatmulShape(group, D, bk), MatmulShape(group, bk, D)],
        vector_flops=6.0 * B * Hq * T,
        dtype=ctx.dtype,
        parallel_grid=B * Hkv * ks,
    )


def _decode_heuristic(ctx: TuningContext) -> Config:
    return {"block_kv": 512, "k_splits": 1}


def _decode_canonical(cfg: Config, ctx: TuningContext) -> Config:
    # The kernel clamps its KV block to the (padded) sequence; block_kv
    # values past that lower to the same program.
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"], _rup(ctx.shape("k")[2], 128))
    return c


def _decode_runner(cfg: Config, ctx: TuningContext):
    q_s, k_s = ctx.shape("q"), ctx.shape("k")
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], q_s, dtype)
    k = _rand(keys[1], k_s, dtype)
    v = _rand(keys[2], k_s, dtype)
    from repro.kernels.decode_attention import decode_attention
    fn = jax.jit(functools.partial(decode_attention, **cfg))
    return KernelRunner(fn, q, k, v)


DECODE_ATTENTION = TunableKernel(
    name="decode_attention",
    space=decode_attention_space(),
    version=2,
    workload_fn=_decode_workload,
    make_runner=_decode_runner,
    heuristic=_decode_heuristic,
    canonicalize=_decode_canonical,
)


def decode(q, k, v, *, kv_len=None, config: Optional[Config] = None,
           tuner: Optional[Autotuner] = None,
           interpret: Optional[bool] = None):
    """Autotuned decode attention. q (B,Hq,D); k,v (B,Hkv,T,D)."""
    from repro.kernels.decode_attention import decode_attention
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q": q.shape, "k": k.shape}, str(q.dtype))
        config = tuner.best_config(DECODE_ATTENTION, ctx)
    return decode_attention(q, k, v, kv_len=kv_len, interpret=interpret,
                            **config)


# ===========================================================================
# Ragged GQA decode (variable per-sequence KV lengths — serving hot path)
# ===========================================================================

def _gqa_decode_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, Hq, D = ctx.shape("q")
    Hkv = ctx.shape("k")[1]
    g = max(1, Hq // Hkv) if cfg.get("pack_gqa", True) else 1
    ib = dtype_bytes(ctx.dtype)
    bk = cfg["block_kv"]
    buf = 2 * (2 * bk * D * ib + g * D * ib)
    scratch = g * D * 4 + 2 * g * LANES * 4
    out = 2 * (g * D * 4 + g * LANES * 4)
    return buf + scratch + out


def gqa_decode_space() -> ConfigSpace:
    sp = ConfigSpace(
        "gqa_decode_ragged",
        [
            Param("block_kv", (128, 256, 512, 1024, 2048)),
            Param("k_splits", (1, 2, 4, 8, 16, 32)),
            Param("pack_gqa", (True, False)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_gqa_decode_vmem))
    sp.constrain(
        "splits<=blocks",
        lambda c, x: c["k_splits"] <= max(1, _cdiv(x.shape("k")[2],
                                                   c["block_kv"])))
    return sp


def _gqa_decode_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    group = max(1, Hq // Hkv)
    pack = cfg.get("pack_gqa", True)
    g = group if pack else 1
    rows = B * Hkv if pack else B * Hq
    # Mean fraction of the padded cache that is actually valid — ragged
    # batches stream proportionally less KV (block skipping on kv_len).
    fill = float(ctx.extra.get("fill", 1.0))
    ib = dtype_bytes(ctx.dtype)
    bk = min(cfg["block_kv"], _rup(T, 128))
    ks = cfg["k_splits"]
    t_pad = _rup(T, bk * ks)
    blocks = t_pad // bk
    run_rows = max(1.0, t_pad * fill)
    flops = 4.0 * B * Hq * T * D * fill
    bytes_kv = 2.0 * rows * run_rows * D * ib     # unpacked re-reads KV/head
    bytes_q = rows * ks * g * D * ib
    bytes_part = 2.0 * rows * ks * g * (D + LANES) * 4
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_part,
        grid_steps=int(rows * max(1, round(blocks * fill))),
        vmem_bytes=_gqa_decode_vmem(cfg, ctx),
        matmuls=[MatmulShape(g, D, bk), MatmulShape(g, bk, D)],
        vector_flops=6.0 * B * Hq * T * fill,
        dtype=ctx.dtype,
        parallel_grid=rows * ks,
    )


def _gqa_decode_heuristic(ctx: TuningContext) -> Config:
    return {"block_kv": 512, "k_splits": 1, "pack_gqa": True}


def _gqa_decode_canonical(cfg: Config, ctx: TuningContext) -> Config:
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"], _rup(ctx.shape("k")[2], 128))
    return c


def _gqa_decode_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.gqa_decode import gqa_decode as gqa_kernel
    q_s, k_s = ctx.shape("q"), ctx.shape("k")
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], q_s, dtype)
    k = _rand(keys[1], k_s, dtype)
    v = _rand(keys[2], k_s, dtype)
    T = k_s[2]
    fill = float(ctx.extra.get("fill", 1.0))
    hi = max(2, int(T * fill)) + 1
    lens = _memo_operand(
        ("randint", 7, q_s[0], hi),
        lambda: jax.random.randint(jax.random.PRNGKey(7), (q_s[0],), 1, hi))
    fn = jax.jit(functools.partial(gqa_kernel, **cfg))
    return KernelRunner(fn, q, k, v, kv_len=lens)


GQA_DECODE_RAGGED = TunableKernel(
    name="gqa_decode_ragged",
    space=gqa_decode_space(),
    version=1,
    workload_fn=_gqa_decode_workload,
    make_runner=_gqa_decode_runner,
    heuristic=_gqa_decode_heuristic,
    canonicalize=_gqa_decode_canonical,
)


def ragged_decode(q, k, v, *, kv_len=None, config: Optional[Config] = None,
                  tuner: Optional[Autotuner] = None,
                  interpret: Optional[bool] = None):
    """Autotuned ragged GQA decode. q (B,Hq,D); k,v (B,Hkv,T,D);
    kv_len (B,) int32 per-request valid lengths."""
    from repro.kernels.gqa_decode import gqa_decode as gqa_kernel
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q": q.shape, "k": k.shape}, str(q.dtype))
        config = tuner.best_config(GQA_DECODE_RAGGED, ctx)
    return gqa_kernel(q, k, v, kv_len=kv_len, interpret=interpret, **config)


# ===========================================================================
# Paged decode (block-table-indexed attention over a shared page pool —
# the continuous-batching serving hot path, see repro/serving/)
# ===========================================================================

def _paged_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, Hq, D = ctx.shape("q")
    Hkv = ctx.shape("k")[1]
    g = max(1, Hq // Hkv) if cfg.get("pack_gqa", True) else 1
    ib = dtype_bytes(ctx.dtype)
    ps = cfg["page_size"]
    # q stays float under kv8 — only the KV pages are int8.
    qb = 4 if "int8" in ctx.dtype else ib
    buf = 2 * (2 * ps * D * ib + g * D * qb)
    if "int8" in ctx.dtype:
        buf += 2 * 2 * ps * 4            # per-token dequant scale blocks
    scratch = g * D * 4 + 2 * g * LANES * 4
    out = 2 * g * D * 4
    return buf + scratch + out


def paged_decode_space() -> ConfigSpace:
    sp = ConfigSpace(
        "paged_decode",
        [
            Param("page_size", (8, 16, 32, 64, 128, 256)),
            Param("block_kv", (8, 16, 32, 64, 128, 256, 512)),
            Param("pack_gqa", (True, False)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_paged_vmem))
    sp.constrain("block_kv%page_size",
                 lambda c, x: c["block_kv"] % c["page_size"] == 0)
    sp.constrain(
        "block_kv<=capacity",
        lambda c, x: c["block_kv"] <= _rup(x.shape("k")[2], c["page_size"]))
    # A deployed pool fixes the page size (extra["page_size"]); tuning for
    # that pool only explores matching layouts. Offline/deployment tuning
    # (no extra) sweeps page_size freely and the winner sizes the pool.
    sp.constrain(
        "page_size==pool",
        lambda c, x: ("page_size" not in x.extra
                      or c["page_size"] == x.extra["page_size"]))
    return sp


def _paged_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    group = max(1, Hq // Hkv)
    pack = cfg.get("pack_gqa", True)
    g = group if pack else 1
    rows = B * Hkv if pack else B * Hq
    fill = float(ctx.extra.get("fill", 1.0))
    ib = dtype_bytes(ctx.dtype)
    ps = cfg["page_size"]
    bk = min(cfg["block_kv"], _rup(T, ps))
    pages = _cdiv(_rup(T, ps), ps)
    # Super-blocks skip at block_kv granularity, so the streamed fraction is
    # quantized up to block_kv — small pages in big blocks re-read tails.
    run_rows = max(1.0, _rup(max(1, int(T * fill)), bk))
    flops = 4.0 * B * Hq * T * D * fill
    quantized = "int8" in ctx.dtype
    bytes_kv = 2.0 * rows * run_rows * D * ib
    if quantized:
        bytes_kv += 2.0 * rows * run_rows * 4   # per-token dequant scales
    # q stays float under the kv8 policy — only the pools are int8.
    bytes_q = rows * g * D * (4 if quantized else ib)
    bytes_tbl = rows * pages * 4 + B * 4        # block table + lens (SMEM)
    bytes_o = rows * g * D * 4
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_tbl + bytes_o,
        grid_steps=int(rows * max(1, round(pages * fill))),
        vmem_bytes=_paged_vmem(cfg, ctx),
        matmuls=[MatmulShape(g, D, ps), MatmulShape(g, ps, D)],
        vector_flops=(6.0 * B * Hq * T
                      + (4.0 * rows * run_rows * D if quantized else 0.0))
        * fill,
        # int8 pools dequantize before the dot: MXU math runs at the
        # float rate (only the HBM stream is int8) — same rule as
        # _kv8_workload.
        dtype="bfloat16" if quantized else ctx.dtype,
        parallel_grid=rows,
    )


def _paged_heuristic(ctx: TuningContext) -> Config:
    # The vLLM-style hard-coded default: 16-token pages, one page per step.
    ps = int(ctx.extra.get("page_size", 16))
    return {"page_size": ps, "block_kv": ps, "pack_gqa": True}


def _paged_canonical(cfg: Config, ctx: TuningContext) -> Config:
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"],
                        _rup(ctx.shape("k")[2], c["page_size"]))
    return c


def _paged_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    """Build a filled pool + block tables from the logical (q, k) shapes.

    Page 0 is the reserved scratch page (never mapped); each sequence owns
    a contiguous run of page ids, lengths are ragged via extra["fill"].
    An "int8" context builds quantized pools (per-token absmax scales in
    parallel scale pools — the kv8 policy layout); q stays float32.
    """
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    quantized = "int8" in ctx.dtype
    dtype = jnp.float32 if quantized else jnp.dtype(ctx.dtype)
    ps = int((cfg or {}).get("page_size",
                             ctx.extra.get("page_size", 16)))
    pages_per_seq = _cdiv(T, ps)
    n_pages = 1 + B * pages_per_seq
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], (B, Hq, D), dtype)
    kp = _rand(keys[1], (Hkv, n_pages, ps, D), dtype)
    vp = _rand(keys[2], (Hkv, n_pages, ps, D), dtype)
    tbl = _memo_operand(
        ("pagetbl", B, pages_per_seq),
        lambda: jnp.arange(1, 1 + B * pages_per_seq, dtype=jnp.int32)
        .reshape(B, pages_per_seq))
    fill = float(ctx.extra.get("fill", 1.0))
    hi = max(2, int(T * fill)) + 1
    lens = _memo_operand(
        ("randint", 7, B, hi),
        lambda: jax.random.randint(jax.random.PRNGKey(7), (B,), 1, hi))
    if not quantized:
        return (q, kp, vp, tbl, lens), {}
    kq, ks, vq, vs = _memo_operand(
        ("int8pool", (Hkv, n_pages, ps, D)),
        lambda: _quantize_kv_pair(kp, vp))
    return (q, kq, vq, tbl, lens), {"k_scales": ks, "v_scales": vs}


def _quantize_kv_pair(k, v):
    # The shared kv8 wire-format contract — identical to what the model
    # cache-append paths write (quant/calibrate.py::quantize_kv).
    from repro.quant.calibrate import quantize_kv
    return quantize_kv(k, v)


def _paged_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.paged_decode import paged_decode as paged_kernel
    args, kwargs = _paged_operands(ctx, cfg)
    fn = jax.jit(functools.partial(paged_kernel, block_kv=cfg["block_kv"],
                                   pack_gqa=cfg["pack_gqa"]))
    return KernelRunner(fn, *args, **kwargs)


PAGED_DECODE = TunableKernel(
    name="paged_decode",
    space=paged_decode_space(),
    version=1,
    workload_fn=_paged_workload,
    make_runner=_paged_runner,
    heuristic=_paged_heuristic,
    canonicalize=_paged_canonical,
)


def paged_decode(q, k_pages, v_pages, block_tables, kv_len, *,
                 layer=0, k_scales=None, v_scales=None,
                 scale: Optional[float] = None,
                 config: Optional[Config] = None,
                 tuner: Optional[Autotuner] = None,
                 interpret: Optional[bool] = None):
    """Autotuned paged decode. q (B,Hq,D); k/v_pages (Hkv,P,page_size,D),
    or the stacked pool of every layer (L,Hkv,P,page_size,D) read in place
    at ``layer``; block_tables (B,max_pages) int32; kv_len (B,) int32.
    Int8 pools (the kv8 policy) pass per-token ``k_scales``/``v_scales``
    (Hkv,P,page_size) f32, or (L,Hkv,P,1,page_size) beside a stacked pool
    — the context dtype becomes "int8", so int8 and float pools tune (and
    cache) as distinct scenarios. The tuning context holds one layer's
    dims, so a stacked pool tunes as its layers' pool does.

    The pool layout pins ``page_size``, so the runtime lookup context
    carries it in ``extra`` and only matching configs are explored; the
    remaining tunables (block_kv, pack_gqa) dispatch to the kernel.

    This is the serving hot path, so the tuner-dispatch route (no explicit
    ``config=``) runs under the kernel guard when active: a config that
    raises or yields non-finite output is quarantined and the call degrades
    through the runner-up portfolio down to the ``ref.py`` oracle.
    """
    from repro.kernels.paged_decode import paged_decode as paged_kernel
    ps = k_pages.shape[-2]
    guarded = config is None
    ctx = None
    _ps_values = next(p.values for p in PAGED_DECODE.space.params
                      if p.name == "page_size")
    if config is None and ps not in _ps_values:
        # Pool laid out with an off-space page size (tiny test pools):
        # nothing to tune — one page per step, packed heads.
        config = {"block_kv": ps, "pack_gqa": True}
        tuner = None
    if config is None:
        tuner = tuner or default_tuner()
        B, Hq, D = q.shape
        Hkv = k_pages.shape[-4]
        T = block_tables.shape[1] * ps
        ctx = _ctx(tuner, {"q": (B, Hq, D), "k": (B, Hkv, T, D)},
                   str(k_pages.dtype), page_size=ps)
        config = tuner.best_config(PAGED_DECODE, ctx)
        if tuner is not None:
            tuner.record_dispatch(PAGED_DECODE.name, ctx, config)

    def run(cfg):
        c = dict(cfg)
        c.pop("page_size", None)
        return paged_kernel(q, k_pages, v_pages, block_tables, kv_len,
                            layer=layer, k_scales=k_scales,
                            v_scales=v_scales, scale=scale,
                            interpret=interpret, **c)

    if guarded and _guard_active():
        def ref_run():
            from repro.kernels import ref
            return ref.paged_decode(q, k_pages, v_pages, block_tables,
                                    kv_len, layer=layer, k_scales=k_scales,
                                    v_scales=v_scales, scale=scale)
        return _guarded_dispatch(PAGED_DECODE, ctx, config, run, ref_run,
                                 tuner)
    return _timed_dispatch(PAGED_DECODE, ctx, config, tuner, run)


# ===========================================================================
# Paged verify (speculative decoding: score K draft positions per sequence
# in one launch — a ragged kv_len+K variant of paged_decode)
# ===========================================================================

def _paged_verify_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, Hq, D = ctx.shape("q")
    Hkv = ctx.shape("k")[1]
    g = max(1, Hq // Hkv) if cfg.get("pack_gqa", True) else 1
    n = cfg["draft_k"] * g               # sublane rows per grid step
    ib = dtype_bytes(ctx.dtype)
    ps = cfg["page_size"]
    qb = 4 if "int8" in ctx.dtype else ib
    buf = 2 * (2 * ps * D * ib + n * D * qb)
    if "int8" in ctx.dtype:
        buf += 2 * 2 * ps * 4            # per-token dequant scale blocks
    scratch = n * D * 4 + 2 * n * LANES * 4
    out = 2 * n * D * 4
    return buf + scratch + out


def paged_verify_space() -> ConfigSpace:
    sp = ConfigSpace(
        "paged_verify",
        [
            Param("draft_k", (2, 3, 4, 6, 8)),
            Param("page_size", (8, 16, 32, 64, 128, 256)),
            Param("block_kv", (8, 16, 32, 64, 128, 256, 512)),
            Param("pack_gqa", (True, False)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_paged_verify_vmem))
    sp.constrain("block_kv%page_size",
                 lambda c, x: c["block_kv"] % c["page_size"] == 0)
    sp.constrain(
        "block_kv<=capacity",
        lambda c, x: c["block_kv"] <= _rup(x.shape("k")[2], c["page_size"]))
    # Layout pins, as in paged_decode: a deployed pool fixes page_size and
    # the engine's speculation depth fixes draft_k (extra); offline tuning
    # (no extra) sweeps both so the shipped DB covers the depth portfolio.
    sp.constrain(
        "page_size==pool",
        lambda c, x: ("page_size" not in x.extra
                      or c["page_size"] == x.extra["page_size"]))
    sp.constrain(
        "draft_k==request",
        lambda c, x: ("draft_k" not in x.extra
                      or c["draft_k"] == x.extra["draft_k"]))
    return sp


def _paged_verify_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    group = max(1, Hq // Hkv)
    pack = cfg.get("pack_gqa", True)
    g = group if pack else 1
    K = cfg["draft_k"]
    rows = B * Hkv if pack else B * Hq
    fill = float(ctx.extra.get("fill", 1.0))
    ib = dtype_bytes(ctx.dtype)
    ps = cfg["page_size"]
    bk = min(cfg["block_kv"], _rup(T, ps))
    pages = _cdiv(_rup(T, ps), ps)
    run_rows = max(1.0, _rup(max(1, int(T * fill)), bk))
    # K query positions amortize the same KV stream: K× the flops of
    # paged_decode, identical page traffic.
    flops = 4.0 * B * Hq * T * D * fill * K
    quantized = "int8" in ctx.dtype
    bytes_kv = 2.0 * rows * run_rows * D * ib
    if quantized:
        bytes_kv += 2.0 * rows * run_rows * 4
    bytes_q = rows * K * g * D * (4 if quantized else ib)
    bytes_tbl = rows * pages * 4 + B * 4
    bytes_o = rows * K * g * D * 4
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_tbl + bytes_o,
        grid_steps=int(rows * max(1, round(pages * fill))),
        vmem_bytes=_paged_verify_vmem(cfg, ctx),
        matmuls=[MatmulShape(K * g, D, ps), MatmulShape(K * g, ps, D)],
        vector_flops=(6.0 * B * Hq * T * K
                      + (4.0 * rows * run_rows * D if quantized else 0.0))
        * fill,
        dtype="bfloat16" if quantized else ctx.dtype,
        parallel_grid=rows,
    )


def _paged_verify_heuristic(ctx: TuningContext) -> Config:
    ps = int(ctx.extra.get("page_size", 16))
    return {"draft_k": int(ctx.extra.get("draft_k", 4)),
            "page_size": ps, "block_kv": ps, "pack_gqa": True}


def _paged_verify_canonical(cfg: Config, ctx: TuningContext) -> Config:
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"],
                        _rup(ctx.shape("k")[2], c["page_size"]))
    return c


def _paged_verify_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    """Pool + block tables as in ``_paged_operands``, plus a K-position
    query block; lengths are ragged but >= K (the engine always scatters
    the K draft positions before verifying them)."""
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    quantized = "int8" in ctx.dtype
    dtype = jnp.float32 if quantized else jnp.dtype(ctx.dtype)
    ps = int((cfg or {}).get("page_size",
                             ctx.extra.get("page_size", 16)))
    K = int((cfg or {}).get("draft_k",
                            ctx.extra.get("draft_k", 4)))
    pages_per_seq = _cdiv(T, ps)
    n_pages = 1 + B * pages_per_seq
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], (B, K, Hq, D), dtype)
    kp = _rand(keys[1], (Hkv, n_pages, ps, D), dtype)
    vp = _rand(keys[2], (Hkv, n_pages, ps, D), dtype)
    tbl = _memo_operand(
        ("pagetbl", B, pages_per_seq),
        lambda: jnp.arange(1, 1 + B * pages_per_seq, dtype=jnp.int32)
        .reshape(B, pages_per_seq))
    fill = float(ctx.extra.get("fill", 1.0))
    hi = max(K + 1, int(T * fill)) + 1
    lens = _memo_operand(
        ("randint", 11, K, B, hi),
        lambda: jax.random.randint(jax.random.PRNGKey(11), (B,), K, hi))
    if not quantized:
        return (q, kp, vp, tbl, lens), {}
    kq, ks, vq, vs = _memo_operand(
        ("int8pool", (Hkv, n_pages, ps, D)),
        lambda: _quantize_kv_pair(kp, vp))
    return (q, kq, vq, tbl, lens), {"k_scales": ks, "v_scales": vs}


def _paged_verify_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.paged_verify import paged_verify as verify_kernel
    args, kwargs = _paged_verify_operands(ctx, cfg)
    fn = jax.jit(functools.partial(verify_kernel, block_kv=cfg["block_kv"],
                                   pack_gqa=cfg["pack_gqa"]))
    return KernelRunner(fn, *args, **kwargs)


PAGED_VERIFY = TunableKernel(
    name="paged_verify",
    space=paged_verify_space(),
    version=1,
    workload_fn=_paged_verify_workload,
    make_runner=_paged_verify_runner,
    heuristic=_paged_verify_heuristic,
    canonicalize=_paged_verify_canonical,
)


def paged_verify(q, k_pages, v_pages, block_tables, kv_len, *,
                 layer=0, k_scales=None, v_scales=None,
                 scale: Optional[float] = None,
                 config: Optional[Config] = None,
                 tuner: Optional[Autotuner] = None,
                 interpret: Optional[bool] = None):
    """Autotuned speculative verify. q (B,K,Hq,D) — K consecutive query
    positions per sequence; k/v_pages (Hkv,P,page_size,D) or stacked
    (L,Hkv,P,page_size,D) read at ``layer``, tuned by one layer's dims;
    block_tables (B,max_pages) int32; kv_len (B,) int32 valid tokens
    **including** the K scattered draft positions. Int8 pools (kv8) pass
    ``k_scales``/``v_scales`` as in ``paged_decode``.

    Both layout pins ride ``extra``: the pool fixes ``page_size`` and the
    engine's speculation depth fixes ``draft_k``, so the tuner explores
    only matching verify block layouts — and K is part of the cache
    signature, making every draft width its own tuning scenario.

    Serving hot path: the tuner-dispatch route runs under the kernel
    guard when a fault plan is active, degrading through runner-up
    configs down to the ``src/repro/kernels/ref.py`` oracle.
    """
    from repro.kernels.paged_verify import paged_verify as verify_kernel
    ps = k_pages.shape[-2]
    B, K, Hq, D = q.shape
    guarded = config is None
    ctx = None
    _ps_values = next(p.values for p in PAGED_VERIFY.space.params
                      if p.name == "page_size")
    _dk_values = next(p.values for p in PAGED_VERIFY.space.params
                      if p.name == "draft_k")
    if config is None and (ps not in _ps_values or K not in _dk_values):
        # Off-space pool layout or draft width (tiny test pools): nothing
        # to tune — one page per step, packed heads.
        config = {"block_kv": ps, "pack_gqa": True}
        tuner = None
    if config is None:
        tuner = tuner or default_tuner()
        Hkv = k_pages.shape[-4]
        T = block_tables.shape[1] * ps
        ctx = _ctx(tuner, {"q": (B, Hq, D), "k": (B, Hkv, T, D)},
                   str(k_pages.dtype), page_size=ps, draft_k=K)
        config = tuner.best_config(PAGED_VERIFY, ctx)
        if tuner is not None:
            tuner.record_dispatch(PAGED_VERIFY.name, ctx, config)

    def run(cfg):
        c = dict(cfg)
        c.pop("page_size", None)
        c.pop("draft_k", None)
        return verify_kernel(q, k_pages, v_pages, block_tables, kv_len,
                             layer=layer, k_scales=k_scales,
                             v_scales=v_scales, scale=scale,
                             interpret=interpret, **c)

    if guarded and _guard_active():
        def ref_run():
            from repro.kernels import ref
            return ref.paged_verify(q, k_pages, v_pages, block_tables,
                                    kv_len, layer=layer, k_scales=k_scales,
                                    v_scales=v_scales, scale=scale)
        return _guarded_dispatch(PAGED_VERIFY, ctx, config, run, ref_run,
                                 tuner)
    return _timed_dispatch(PAGED_VERIFY, ctx, config, tuner, run)


# ===========================================================================
# MLA decode (absorbed latent attention over the compressed KV cache)
# ===========================================================================

def _mla_decode_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, H, C = ctx.shape("q_abs")
    R = ctx.shape("q_rope")[2]
    ib = dtype_bytes(ctx.dtype)
    bk = cfg["block_kv"]
    buf = 2 * (bk * C * ib + bk * R * ib + H * C * ib + H * R * ib)
    scratch = H * C * 4 + 2 * H * LANES * 4
    out = 2 * (H * C * 4 + H * LANES * 4)
    return buf + scratch + out


def mla_decode_space() -> ConfigSpace:
    sp = ConfigSpace(
        "mla_decode",
        [
            Param("block_kv", (128, 256, 512, 1024, 2048)),
            Param("k_splits", (1, 2, 4, 8, 16, 32)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_mla_decode_vmem))
    sp.constrain(
        "splits<=blocks",
        lambda c, x: c["k_splits"] <= max(1, _cdiv(x.shape("ckv")[1],
                                                   c["block_kv"])))
    return sp


def _mla_decode_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, H, C = ctx.shape("q_abs")
    _, T, _ = ctx.shape("ckv")
    R = ctx.shape("q_rope")[2]
    ib = dtype_bytes(ctx.dtype)
    bk = min(cfg["block_kv"], _rup(T, 128))
    ks = cfg["k_splits"]
    t_pad = _rup(T, bk * ks)
    blocks = t_pad // bk
    # scores (C- and R-contractions) + latent context accumulation
    flops = 2.0 * B * H * T * (2 * C + R)
    bytes_kv = B * t_pad * (C + R) * ib           # shared latent cache, read once
    bytes_q = B * ks * H * (C + R) * ib
    bytes_part = 2.0 * B * ks * H * (C + LANES) * 4
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_part,
        grid_steps=B * blocks,
        vmem_bytes=_mla_decode_vmem(cfg, ctx),
        matmuls=[MatmulShape(H, C, bk), MatmulShape(H, R, bk),
                 MatmulShape(H, bk, C)],
        vector_flops=6.0 * B * H * T,
        dtype=ctx.dtype,
        parallel_grid=B * ks,
    )


def _mla_decode_heuristic(ctx: TuningContext) -> Config:
    return {"block_kv": 512, "k_splits": 1}


def _mla_decode_canonical(cfg: Config, ctx: TuningContext) -> Config:
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"], _rup(ctx.shape("ckv")[1], 128))
    return c


def _mla_decode_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.mla_decode import mla_decode as mla_kernel
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    qa = _rand(keys[0], ctx.shape("q_abs"), dtype)
    qr = _rand(keys[1], ctx.shape("q_rope"), dtype)
    ckv = _rand(keys[2], ctx.shape("ckv"), dtype)
    kr = _rand(keys[3], ctx.shape("krope"), dtype)
    scale = float(ctx.extra.get("scale", 1.0))
    fn = jax.jit(functools.partial(mla_kernel, scale=scale, **cfg))
    return KernelRunner(fn, qa, qr, ckv, kr)


MLA_DECODE = TunableKernel(
    name="mla_decode",
    space=mla_decode_space(),
    version=1,
    workload_fn=_mla_decode_workload,
    make_runner=_mla_decode_runner,
    heuristic=_mla_decode_heuristic,
    canonicalize=_mla_decode_canonical,
)


def latent_decode(q_abs, q_rope, ckv, krope, *, kv_len=None,
                  scale: Optional[float] = None,
                  config: Optional[Config] = None,
                  tuner: Optional[Autotuner] = None,
                  interpret: Optional[bool] = None):
    """Autotuned absorbed-MLA decode. q_abs (B,H,C); q_rope (B,H,R);
    ckv (B,T,C); krope (B,T,R). Returns attended latents (B,H,C) f32."""
    from repro.kernels.mla_decode import mla_decode as mla_kernel
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q_abs": q_abs.shape, "q_rope": q_rope.shape,
                           "ckv": ckv.shape, "krope": krope.shape},
                   str(ckv.dtype))
        config = tuner.best_config(MLA_DECODE, ctx)
    return mla_kernel(q_abs, q_rope, ckv, krope, kv_len=kv_len, scale=scale,
                      interpret=interpret, **config)


# ===========================================================================
# RMS norm
# ===========================================================================

def _rms_vmem(cfg: Config, ctx: TuningContext) -> int:
    D = ctx.shape("x")[-1]
    ib = dtype_bytes(ctx.dtype)
    br = cfg["block_rows"]
    return 2 * (br * D * ib * 2) + D * 4 + br * D * 4


def rms_norm_space() -> ConfigSpace:
    sp = ConfigSpace(
        "rms_norm",
        [Param("block_rows", (8, 16, 32, 64, 128, 256, 512, 1024))],
        version=2,
    )
    sp.constrain("vmem", vmem_fits(_rms_vmem))
    return sp


def _rms_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    shape = ctx.shape("x")
    D = shape[-1]
    N = int(math.prod(shape[:-1]))
    ib = dtype_bytes(ctx.dtype)
    br = min(cfg["block_rows"], _rup(N, 8))
    n_blocks = _cdiv(N, br)
    return KernelWorkload(
        flops=0.0,
        hbm_bytes=(2.0 * N * D * ib) + D * 4,
        grid_steps=n_blocks,
        vmem_bytes=_rms_vmem(cfg, ctx),
        vector_flops=4.0 * N * D,
        dtype=ctx.dtype,
        parallel_grid=n_blocks,
    )


RMS_NORM = TunableKernel(
    name="rms_norm",
    space=rms_norm_space(),
    version=2,
    workload_fn=_rms_workload,
    make_runner=lambda cfg, ctx: _rms_runner(cfg, ctx),
    heuristic=lambda ctx: {"block_rows": 128},
)


def _rms_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.rms_norm import rms_norm
    x_s = ctx.shape("x")
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x = _rand(keys[0], x_s, dtype)
    w = _rand(keys[1], (x_s[-1],), dtype)
    fn = jax.jit(functools.partial(rms_norm, **cfg))
    return KernelRunner(fn, x, w)


def rmsnorm(x, weight, *, eps: float = 1e-6, config: Optional[Config] = None,
            tuner: Optional[Autotuner] = None,
            interpret: Optional[bool] = None):
    from repro.kernels.rms_norm import rms_norm
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"x": x.shape}, str(x.dtype))
        config = tuner.best_config(RMS_NORM, ctx)
    return rms_norm(x, weight, eps=eps, interpret=interpret, **config)


# ===========================================================================
# Blocked matmul
# ===========================================================================

def _mm_vmem(cfg: Config, ctx: TuningContext) -> int:
    ib = dtype_bytes(ctx.dtype)
    bm, bn, bk = cfg["block_m"], cfg["block_n"], cfg["block_k"]
    return 2 * (bm * bk + bk * bn) * ib + bm * bn * (4 + 2 * ib)


def matmul_space() -> ConfigSpace:
    sp = ConfigSpace(
        "matmul",
        [
            Param("block_m", (128, 256, 512, 1024)),
            Param("block_n", (128, 256, 512, 1024)),
            Param("block_k", (128, 256, 512, 1024, 2048)),
        ],
        version=2,
    )
    sp.constrain("vmem", vmem_fits(_mm_vmem))
    return sp


def _mm_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    M, K = ctx.shape("x")
    _, N = ctx.shape("y")
    ib = dtype_bytes(ctx.dtype)
    bm = min(cfg["block_m"], _rup(M, 8))
    bn = min(cfg["block_n"], _rup(N, 128))
    bk = min(cfg["block_k"], _rup(K, 128))
    nm, nn, nk = _cdiv(M, bm), _cdiv(N, bn), _cdiv(K, bk)
    bytes_x = nm * nn * nk * bm * bk * ib
    bytes_y = nm * nn * nk * bk * bn * ib
    bytes_o = nm * nn * bm * bn * ib
    return KernelWorkload(
        flops=2.0 * M * K * N,
        hbm_bytes=bytes_x + bytes_y + bytes_o,
        grid_steps=nm * nn * nk,
        vmem_bytes=_mm_vmem(cfg, ctx),
        matmuls=[MatmulShape(bm, bk, bn)],
        dtype=ctx.dtype,
        parallel_grid=nm * nn,
    )


def _mm_canonical(cfg: Config, ctx: TuningContext) -> Config:
    M, K = ctx.shape("x")
    N = ctx.shape("y")[1]
    return {"block_m": min(cfg["block_m"], _rup(M, 8)),
            "block_n": min(cfg["block_n"], _rup(N, 128)),
            "block_k": min(cfg["block_k"], _rup(K, 128))}


def _mm_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.matmul import matmul as mm
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x = _rand(keys[0], ctx.shape("x"), dtype)
    y = _rand(keys[1], ctx.shape("y"), dtype)
    fn = jax.jit(functools.partial(mm, **cfg))
    return KernelRunner(fn, x, y)


MATMUL = TunableKernel(
    name="matmul",
    space=matmul_space(),
    version=2,
    workload_fn=_mm_workload,
    make_runner=_mm_runner,
    heuristic=lambda ctx: {"block_m": 256, "block_n": 256, "block_k": 256},
    canonicalize=_mm_canonical,
)


def matmul(x, y, *, config: Optional[Config] = None,
           tuner: Optional[Autotuner] = None,
           interpret: Optional[bool] = None):
    from repro.kernels.matmul import matmul as mm
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"x": x.shape, "y": y.shape}, str(x.dtype))
        config = tuner.best_config(MATMUL, ctx)
    return mm(x, y, interpret=interpret, **config)


# ===========================================================================
# Quantized GEMM (w8a8): int8×int8→int32 MXU accumulate, fused dequant
# ===========================================================================

def _w8a8_vmem(cfg: Config, ctx: TuningContext) -> int:
    bm, bn, bk = cfg["block_m"], cfg["block_n"], cfg["block_k"]
    buf = 2 * (bm * bk + bk * bn) * 1            # int8 operand tiles
    acc = bm * bn * 4                            # int32 / f32 accumulator
    out = 2 * bm * bn * 4                        # f32 output tile
    scales = (bm + bn) * 4 if cfg.get("scale_gran") == "per_channel" else 8
    return buf + acc + out + scales


def matmul_w8a8_space() -> ConfigSpace:
    sp = ConfigSpace(
        "matmul_w8a8",
        [
            Param("block_m", (128, 256, 512, 1024)),
            Param("block_n", (128, 256, 512, 1024)),
            Param("block_k", (128, 256, 512, 1024, 2048)),
            Param("dequant", ("epilogue", "inline")),
            Param("scale_gran", ("per_channel", "per_tensor")),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_w8a8_vmem))
    # Runtime operands arrive calibrated at a fixed granularity (their
    # scale shapes), pinning the tunable — exactly as a deployed pool pins
    # paged_decode's page_size. Offline deployment sweeps (no extra) leave
    # it free and the winner tells the calibration pipeline what to emit.
    sp.constrain(
        "scale_gran==operands",
        lambda c, x: ("scale_gran" not in x.extra
                      or c["scale_gran"] == x.extra["scale_gran"]))
    return sp


def _w8a8_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    M, K = ctx.shape("x")
    _, N = ctx.shape("y")
    bm = min(cfg["block_m"], _rup(M, 8))
    bn = min(cfg["block_n"], _rup(N, 128))
    bk = min(cfg["block_k"], _rup(K, 128))
    nm, nn, nk = _cdiv(M, bm), _cdiv(N, bn), _cdiv(K, bk)
    bytes_x = nm * nn * nk * bm * bk * 1         # int8 operands
    bytes_y = nm * nn * nk * bk * bn * 1
    bytes_o = nm * nn * bm * bn * 4              # f32 output
    bytes_s = (M + N) * 4 if cfg["scale_gran"] == "per_channel" else 8
    # Dequant cost: the epilogue scales each output element once; inline
    # converts + scales every K-block partial (nk× the VPU work) in
    # exchange for an f32 accumulator.
    vflops = 3.0 * M * N * (nk if cfg["dequant"] == "inline" else 1)
    return KernelWorkload(
        flops=2.0 * M * K * N,
        hbm_bytes=bytes_x + bytes_y + bytes_o + bytes_s,
        grid_steps=nm * nn * nk,
        vmem_bytes=_w8a8_vmem(cfg, ctx),
        matmuls=[MatmulShape(bm, bk, bn)],
        vector_flops=vflops,
        dtype="int8",            # the int8 MXU path (ChipSpec.flops_for_dtype)
        parallel_grid=nm * nn,
    )


def _w8a8_heuristic(ctx: TuningContext) -> Config:
    # What a sensible port of the bf16 matmul default would hard-code:
    # same tiling triple, epilogue dequant, per-channel scales.
    gran = ctx.extra.get("scale_gran", "per_channel")
    return {"block_m": 256, "block_n": 256, "block_k": 256,
            "dequant": "epilogue", "scale_gran": gran}


def _w8a8_canonical(cfg: Config, ctx: TuningContext) -> Config:
    M, K = ctx.shape("x")
    N = ctx.shape("y")[1]
    c = dict(cfg)
    c["block_m"] = min(cfg["block_m"], _rup(M, 8))
    c["block_n"] = min(cfg["block_n"], _rup(N, 128))
    c["block_k"] = min(cfg["block_k"], _rup(K, 128))
    # dequant stays: even with one K step, inline vs epilogue lower to
    # distinct programs (f32 vs int32 accumulator scratch).
    return c


def _w8a8_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.matmul_int8 import matmul_w8a8 as mm8
    args, _ = _w8a8_operands(ctx, cfg)
    fn = jax.jit(functools.partial(mm8, **cfg))
    return KernelRunner(fn, *args)


MATMUL_W8A8 = TunableKernel(
    name="matmul_w8a8",
    space=matmul_w8a8_space(),
    version=1,
    workload_fn=_w8a8_workload,
    make_runner=_w8a8_runner,
    heuristic=_w8a8_heuristic,
    canonicalize=_w8a8_canonical,
)


def matmul_w8a8(x, w, x_scale, w_scale, *, config: Optional[Config] = None,
                tuner: Optional[Autotuner] = None,
                interpret: Optional[bool] = None):
    """Autotuned w8a8 GEMM. x (M,K) int8; w (K,N) int8; x_scale (M,1) or
    scalar; w_scale (1,N) or scalar. Returns (M,N) float32 with the
    calibration scales fused into the kernel."""
    from repro.kernels.matmul_int8 import matmul_w8a8 as mm8
    # Granularity is decided by the weight scale's layout (a per-token
    # activation scale with M == 1 is legitimately scalar-sized).
    gran = ("per_tensor"
            if int(math.prod(jnp.shape(w_scale) or (1,))) == 1
            else "per_channel")
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"x": x.shape, "y": w.shape}, "int8",
                   scale_gran=gran)
        config = tuner.best_config(MATMUL_W8A8, ctx)
    cfg = dict(config)
    cfg.setdefault("scale_gran", gran)
    return mm8(x, w, x_scale, w_scale, interpret=interpret, **cfg)


# ===========================================================================
# Int8-KV ragged GQA decode (kv8): in-kernel dequant over a quantized cache
# ===========================================================================

def _kv8_vmem(cfg: Config, ctx: TuningContext) -> int:
    B, Hq, D = ctx.shape("q")
    Hkv = ctx.shape("k")[1]
    g = max(1, Hq // Hkv) if cfg.get("pack_gqa", True) else 1
    bk = cfg["block_kv"]
    buf = 2 * (2 * bk * D * 1 + 2 * bk * 4 + g * D * 4)   # int8 kv + scales
    scratch = g * D * 4 + 2 * g * LANES * 4
    out = 2 * (g * D * 4 + g * LANES * 4)
    return buf + scratch + out


def gqa_decode_kv8_space() -> ConfigSpace:
    sp = ConfigSpace(
        "gqa_decode_kv8",
        [
            Param("block_kv", (128, 256, 512, 1024, 2048, 4096)),
            Param("k_splits", (1, 2, 4, 8, 16, 32)),
            Param("pack_gqa", (True, False)),
        ],
        version=1,
    )
    sp.constrain("vmem", vmem_fits(_kv8_vmem))
    sp.constrain(
        "splits<=blocks",
        lambda c, x: c["k_splits"] <= max(1, _cdiv(x.shape("k")[2],
                                                   c["block_kv"])))
    return sp


def _kv8_workload(cfg: Config, ctx: TuningContext) -> KernelWorkload:
    B, Hq, D = ctx.shape("q")
    _, Hkv, T, _ = ctx.shape("k")
    group = max(1, Hq // Hkv)
    pack = cfg.get("pack_gqa", True)
    g = group if pack else 1
    rows = B * Hkv if pack else B * Hq
    fill = float(ctx.extra.get("fill", 1.0))
    bk = min(cfg["block_kv"], _rup(T, 128))
    ks = cfg["k_splits"]
    t_pad = _rup(T, bk * ks)
    blocks = t_pad // bk
    run_rows = max(1.0, t_pad * fill)
    flops = 4.0 * B * Hq * T * D * fill
    # int8 cache + f32 per-token scales: the bandwidth win vs gqa_decode
    # is the whole point — D bytes per token instead of 2·D, plus 8 for
    # the two scales.
    bytes_kv = rows * run_rows * (2.0 * D * 1 + 2 * 4)
    bytes_q = rows * ks * g * D * 4
    bytes_part = 2.0 * rows * ks * g * (D + LANES) * 4
    return KernelWorkload(
        flops=flops,
        hbm_bytes=bytes_kv + bytes_q + bytes_part,
        grid_steps=int(rows * max(1, round(blocks * fill))),
        vmem_bytes=_kv8_vmem(cfg, ctx),
        matmuls=[MatmulShape(g, D, bk), MatmulShape(g, bk, D)],
        # dequant (2 muls/element) rides the softmax pipeline on the VPU
        vector_flops=(6.0 * B * Hq * T + 4.0 * rows * run_rows * D) * fill,
        dtype="bfloat16",        # post-dequant MXU math runs at float peak
        parallel_grid=rows * ks,
    )


def _kv8_heuristic(ctx: TuningContext) -> Config:
    return {"block_kv": 512, "k_splits": 1, "pack_gqa": True}


def _kv8_canonical(cfg: Config, ctx: TuningContext) -> Config:
    c = dict(cfg)
    c["block_kv"] = min(c["block_kv"], _rup(ctx.shape("k")[2], 128))
    return c


def _kv8_runner(cfg: Config, ctx: TuningContext):
    from repro.kernels.gqa_decode_kv8 import gqa_decode_kv8 as kv8_kernel
    args, kwargs = _kv8_operands(ctx, cfg)
    fn = jax.jit(functools.partial(kv8_kernel, **cfg))
    return KernelRunner(fn, *args, **kwargs)


GQA_DECODE_KV8 = TunableKernel(
    name="gqa_decode_kv8",
    space=gqa_decode_kv8_space(),
    version=1,
    workload_fn=_kv8_workload,
    make_runner=_kv8_runner,
    heuristic=_kv8_heuristic,
    canonicalize=_kv8_canonical,
)


def ragged_decode_kv8(q, k, v, k_scale, v_scale, *, kv_len=None,
                      config: Optional[Config] = None,
                      tuner: Optional[Autotuner] = None,
                      interpret: Optional[bool] = None):
    """Autotuned int8-KV ragged decode. q (B,Hq,D) float; k, v
    (B,Hkv,T,D) int8; k_scale, v_scale (B,Hkv,T) f32 per-token scales;
    kv_len (B,) int32 valid lengths."""
    from repro.kernels.gqa_decode_kv8 import gqa_decode_kv8 as kv8_kernel
    if config is None:
        tuner = tuner or default_tuner()
        ctx = _ctx(tuner, {"q": q.shape, "k": k.shape}, "int8")
        config = tuner.best_config(GQA_DECODE_KV8, ctx)
    return kv8_kernel(q, k, v, k_scale, v_scale, kv_len=kv_len,
                      interpret=interpret, **config)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _rup(a: int, b: int) -> int:
    return -(-a // b) * b


# ===========================================================================
# Operand builders — (ctx, config) -> (args, kwargs) accepted by BOTH the
# entry point and the ref.py oracle. Declared on each KernelSpec so the
# registry-driven conformance sweep (tests/test_kernel_oracles.py) can
# exercise any kernel without per-kernel glue.
# ===========================================================================

def _qkv_operands(ctx: TuningContext):
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    return (_rand(keys[0], ctx.shape("q"), dtype),
            _rand(keys[1], ctx.shape("k"), dtype),
            _rand(keys[2], ctx.shape("k"), dtype))


def _attention_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    return _qkv_operands(ctx), {
        "causal": bool(ctx.extra.get("causal", True)),
        "window": ctx.extra.get("window") or None,
    }


def _decode_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    return _qkv_operands(ctx), {}


def _ragged_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    B = ctx.shape("q")[0]
    T = ctx.shape("k")[2]
    fill = float(ctx.extra.get("fill", 1.0))
    hi = max(2, int(T * fill)) + 1
    lens = _memo_operand(
        ("randint", 7, B, hi),
        lambda: jax.random.randint(jax.random.PRNGKey(7), (B,), 1, hi))
    return _qkv_operands(ctx), {"kv_len": lens}


def _mla_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    args = (_rand(keys[0], ctx.shape("q_abs"), dtype),
            _rand(keys[1], ctx.shape("q_rope"), dtype),
            _rand(keys[2], ctx.shape("ckv"), dtype),
            _rand(keys[3], ctx.shape("krope"), dtype))
    return args, {"scale": float(ctx.extra.get("scale", 1.0))}


def _rms_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x_s = ctx.shape("x")
    return (_rand(keys[0], x_s, dtype),
            _rand(keys[1], (x_s[-1],), dtype)), {}


def _mm_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    dtype = jnp.dtype(ctx.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    return (_rand(keys[0], ctx.shape("x"), dtype),
            _rand(keys[1], ctx.shape("y"), dtype)), {}


def _w8a8_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    """Quantized GEMM operands at the granularity the config (or the
    context pin) asks for — operand *layout* is config-dependent, like
    paged_decode's pool."""
    gran = ((cfg or {}).get("scale_gran")
            or ctx.extra.get("scale_gran", "per_channel"))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x_s, y_s = ctx.shape("x"), ctx.shape("y")

    def build():
        from repro.quant import calibrate
        x = _rand(keys[0], x_s, jnp.float32)
        w = _rand(keys[1], y_s, jnp.float32)
        if gran == "per_tensor":
            xs = calibrate.absmax_scale(x)
            ws = calibrate.absmax_scale(w)
        else:
            xs = calibrate.absmax_scale(x, axis=-1)      # (M, 1)
            ws = calibrate.absmax_scale(w, axis=0)       # (1, N)
        return (calibrate.quantize(x, xs), calibrate.quantize(w, ws),
                xs, ws)

    args = _memo_operand(("w8a8", x_s, y_s, gran), build)
    return args, {}


def _kv8_operands(ctx: TuningContext, cfg: Optional[Config] = None):
    """Int8-KV decode operands: float q, per-token-quantized cache."""
    B, Hq, D = ctx.shape("q")
    k_s = ctx.shape("k")
    T = k_s[2]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], (B, Hq, D), jnp.float32)
    kq, ks, vq, vs = _memo_operand(
        ("int8kv", k_s),
        lambda: _quantize_kv_pair(_rand(keys[1], k_s, jnp.float32),
                                  _rand(keys[2], k_s, jnp.float32)))
    fill = float(ctx.extra.get("fill", 1.0))
    hi = max(2, int(T * fill)) + 1
    lens = _memo_operand(
        ("randint", 7, B, hi),
        lambda: jax.random.randint(jax.random.PRNGKey(7), (B,), 1, hi))
    return (q, kq, vq, ks, vs), {"kv_len": lens}


# ===========================================================================
# Registry — the single enumeration point for every consumer
# ===========================================================================

def _register_builtin_kernels() -> None:
    from repro.kernels import ref
    from repro.kernels.registry import BenchCase, KernelSpec, register

    register(KernelSpec(
        tunable=FLASH_ATTENTION,
        scenarios=("prefill", "training", "gqa"),
        reference=ref.attention,
        entry_point=attention,
        operands=_attention_operands,
        description="Flash attention forward (prefill / training)",
        bench_cases=(
            BenchCase("s512", {"q": (1, 4, 512, 128), "k": (1, 1, 512, 128)},
                      extra={"causal": True, "window": 0}),
            BenchCase("train4k",
                      {"q": (8, 32, 4096, 128), "k": (8, 8, 4096, 128)},
                      dtype="bfloat16",
                      extra={"causal": True, "window": 0}, scale="paper"),
            BenchCase("prefill32k",
                      {"q": (1, 32, 32768, 128), "k": (1, 8, 32768, 128)},
                      dtype="bfloat16",
                      extra={"causal": True, "window": 0}, scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=FLASH_ATTENTION_BWD,
        scenarios=("training",),
        entry_point=attention_bwd,
        description="Flash attention backward (dq/dk/dv recompute)",
        bench_cases=(
            BenchCase("train4k",
                      {"q": (8, 32, 4096, 128), "k": (8, 8, 4096, 128)},
                      dtype="bfloat16",
                      extra={"causal": True, "window": 0}, scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=DECODE_ATTENTION,
        scenarios=("decode", "gqa"),
        reference=ref.decode_attention,
        entry_point=decode,
        operands=_decode_operands,
        description="Flash-decode attention (one token vs KV cache)",
        bench_cases=(
            BenchCase("d1024", {"q": (2, 4, 128), "k": (2, 1, 1024, 128)}),
            BenchCase("decode32k",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="bfloat16", scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=GQA_DECODE_RAGGED,
        scenarios=("decode", "gqa", "ragged", "serving"),
        reference=ref.gqa_decode,
        entry_point=ragged_decode,
        operands=_ragged_operands,
        description="Ragged batched GQA decode (per-request KV lengths)",
        bench_cases=(
            BenchCase("r1024", {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      extra={"fill": 0.5}),
            BenchCase("serve32k",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="bfloat16", extra={"fill": 0.5}, scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=PAGED_DECODE,
        scenarios=("decode", "gqa", "ragged", "serving", "paged", "quant"),
        reference=ref.paged_decode,
        entry_point=paged_decode,
        operands=_paged_operands,
        description="Paged-KV decode over block tables (continuous "
                    "batching page pool; int8 pages under the kv8 policy)",
        bench_cases=(
            BenchCase("p1024", {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      extra={"fill": 0.5}),
            BenchCase("p1024_kv8",
                      {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      dtype="int8", extra={"fill": 0.5}),
            BenchCase("pool32k",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="bfloat16", extra={"fill": 0.5}, scale="paper"),
            BenchCase("pool32k_kv8",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="int8", extra={"fill": 0.5}, scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=PAGED_VERIFY,
        scenarios=("decode", "gqa", "ragged", "serving", "paged", "quant",
                   "speculative"),
        reference=ref.paged_verify,
        entry_point=paged_verify,
        operands=_paged_verify_operands,
        description="Speculative batched verify: K draft positions per "
                    "sequence in one launch over the paged-KV pool "
                    "(ragged kv_len+K causal tails; int8 pages under kv8)",
        bench_cases=(
            BenchCase("v1024", {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      extra={"fill": 0.5, "draft_k": 4}),
            BenchCase("v1024_kv8",
                      {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      dtype="int8", extra={"fill": 0.5, "draft_k": 4}),
            BenchCase("vpool32k",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="bfloat16", extra={"fill": 0.5, "draft_k": 4},
                      scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=MLA_DECODE,
        scenarios=("decode", "mla", "serving"),
        reference=ref.mla_decode,
        entry_point=latent_decode,
        operands=_mla_operands,
        description="Absorbed-MLA decode over the compressed latent cache",
        bench_cases=(
            BenchCase("m1024", {"q_abs": (2, 4, 256), "q_rope": (2, 4, 64),
                                "ckv": (2, 1024, 256),
                                "krope": (2, 1024, 64)}),
            BenchCase("dsv2_32k",
                      {"q_abs": (8, 16, 512), "q_rope": (8, 16, 64),
                       "ckv": (8, 32768, 512), "krope": (8, 32768, 64)},
                      dtype="bfloat16", scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=RMS_NORM,
        scenarios=("prefill", "decode", "training"),
        reference=ref.rms_norm,
        entry_point=rmsnorm,
        operands=_rms_operands,
        description="RMS layer norm",
        bench_cases=(
            BenchCase("r1024x2048", {"x": (1024, 2048)}),
            BenchCase("r8192x4096", {"x": (8192, 4096)}, dtype="bfloat16",
                      scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=MATMUL,
        scenarios=("prefill", "training"),
        reference=ref.matmul,
        entry_point=matmul,
        operands=_mm_operands,
        description="Blocked matmul",
        bench_cases=(
            BenchCase("m256", {"x": (256, 256), "y": (256, 256)}),
            BenchCase("mm8k", {"x": (8192, 8192), "y": (8192, 8192)},
                      dtype="bfloat16", scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=MATMUL_W8A8,
        scenarios=("prefill", "training", "serving", "quant"),
        precision="int8",
        reference=ref.matmul_w8a8,
        entry_point=matmul_w8a8,
        operands=_w8a8_operands,
        description="w8a8 GEMM: int8×int8→int32 MXU accumulate with "
                    "fused per-channel/per-tensor dequant",
        bench_cases=(
            BenchCase("m256", {"x": (256, 256), "y": (256, 256)},
                      dtype="int8"),
            BenchCase("proj4k", {"x": (512, 4096), "y": (4096, 4096)},
                      dtype="int8", scale="paper"),
            BenchCase("mm8k", {"x": (8192, 8192), "y": (8192, 8192)},
                      dtype="int8", scale="paper"),
        ),
    ))
    register(KernelSpec(
        tunable=GQA_DECODE_KV8,
        scenarios=("decode", "gqa", "ragged", "serving", "quant"),
        precision="int8",
        reference=ref.gqa_decode_kv8,
        entry_point=ragged_decode_kv8,
        operands=_kv8_operands,
        description="Ragged GQA decode over an int8 KV cache "
                    "(per-token scales, in-kernel dequant)",
        bench_cases=(
            BenchCase("r1024", {"q": (2, 8, 128), "k": (2, 2, 1024, 128)},
                      dtype="int8", extra={"fill": 0.5}),
            BenchCase("serve32k",
                      {"q": (16, 32, 128), "k": (16, 8, 32768, 128)},
                      dtype="int8", extra={"fill": 0.5}, scale="paper"),
        ),
    ))


_register_builtin_kernels()
