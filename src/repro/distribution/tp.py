"""Tensor-parallel inference: explicit shard_map serving with local shapes.

The GSPMD path (sharding.py + launch/steps.py) annotates global tensors and
lets XLA partition — good for training, but the kernels trace with *global*
shapes, so the autotuner never sees what each device actually runs. This
module is the serving-side alternative: model code executes inside a
``shard_map`` body where

  * attention q/k/v projections are column-parallel (head-sharded), the
    output projection row-parallel with an explicit psum
    (``attention._proj_out`` → ``sharding.tp_psum``),
  * MLP ``wi`` is column-parallel (ff-sharded), ``wo`` row-parallel + psum
    (``layers.apply_mlp``),
  * norms, embeddings, and logits are replicated (activations between
    blocks are replicated, so TP=N runs N-way compute on every projection
    with exactly two all-reduces per layer),
  * the KV cache — dense per-request buffers or the paged pool — is
    sharded on the kv-head axis and never leaves its shard.

Because the body runs on per-shard *local* shapes, every kernel entry
point (``ops.ragged_decode``, ``ops.paged_decode``, ...) builds its
TuningContext from the shapes the device really launches, stamped with the
mesh signature (``sharding.tensor_parallel``) — the shard-aware tuning
this PR exists for: a TP=4 shard with 8 local q heads is a different
tuning scenario from an unsharded 8-head model, and the cache keys keep
them distinct (DESIGN.md §11).

Weight layout subtlety: swiglu ``wi`` stores [gate | up] concatenated on
the ff axis. A contiguous shard of that axis would hand shard i a slice of
the gate half only, so ``shard_params`` pre-permutes wi columns to
[g_0|u_0|g_1|u_1|...] — each shard's local ``jnp.split`` then recovers its
own (gate, up) pair, and the row-sharded ``wo`` (original ff order, shard
i owns rows i·f/tp:(i+1)·f/tp) matches exactly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.distribution.sharding import mesh_signature, tensor_parallel
from repro.models.config import ModelConfig

TP_AXIS = "model"

# Logical param axes (ParamSpec.axes) sharded over the TP axis. vocab /
# d_model stay replicated: serving batches are small, and replicated
# embeddings keep logits bitwise-identical across shards (greedy sampling
# needs no cross-shard argmax protocol).
_TP_PARAM_AXES = frozenset({"heads", "kv_heads", "ff"})

# Cache leaf → axis (negative, so stacked-layer leading dims don't matter)
# carrying kv heads, sharded over TP.
_CACHE_TP_AXIS = {
    # dense decode caches: k/v (B, slots, Hkv, D), scales (B, slots, Hkv)
    "k": -2, "v": -2, "k_scale": -1, "v_scale": -1,
    # paged pools: pages (L, Hkv, P, page_size, D), scales
    # (L, Hkv, P, 1, page_size)
    "k_pages": -4, "v_pages": -4, "k_scales": -4, "v_scales": -4,
}


def make_tp_mesh(tp: int) -> Mesh:
    """1-D ("model",) mesh over ``tp`` devices. Callers must launch with
    enough devices (CPU hosts: XLA_FLAGS=--xla_force_host_platform_
    device_count=N before first jax init)."""
    n = len(jax.devices())
    if tp > n:
        raise ValueError(
            f"tp={tp} but only {n} jax device(s); on a CPU host set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            f"before importing jax")
    return jax.make_mesh((tp,), (TP_AXIS,), axis_types=(AxisType.Auto,))


def tp_degree(mesh: Mesh) -> int:
    return int(mesh.shape[TP_AXIS])


def check_tp_supported(cfg: ModelConfig, tp: int) -> None:
    """TP serving covers dense RoPE GQA/MHA transformer stacks — the same
    family the paged path serves. Everything else fails loudly."""
    kinds = set(cfg.layer_kinds())
    if kinds != {"attn_mlp"} or cfg.mla is not None or cfg.window is not None \
            or cfg.learned_pos or cfg.n_prefix or cfg.family == "encdec":
        raise NotImplementedError(
            f"tensor-parallel serving supports dense RoPE attention+MLP "
            f"stacks; {cfg.name!r} has layers {sorted(kinds)}")
    for dim, name in ((cfg.n_heads, "n_heads"), (cfg.n_kv_heads, "n_kv_heads"),
                      (cfg.d_ff, "d_ff")):
        if dim % tp != 0:
            raise ValueError(
                f"{cfg.name!r}: {name}={dim} not divisible by tp={tp}")


def local_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-shard view of the architecture: heads and ff divided by tp.
    Model code inside the shard_map body runs unchanged against this config
    — reshape arithmetic, GQA group size (hq/hkv ratio preserved), and the
    kernel dispatch all see honest local dimensions."""
    if tp == 1:
        return cfg
    check_tp_supported(cfg, tp)
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // tp, n_kv_heads=cfg.n_kv_heads // tp,
        d_ff=cfg.d_ff // tp)


# ---------------------------------------------------------------------------
# Partition-spec trees
# ---------------------------------------------------------------------------

def param_partition_specs(cfg: ModelConfig):
    """PartitionSpec pytree matching ``lm.lm_specs(cfg)``: column-parallel
    wq/wk/wv/wi (head/ff axes), row-parallel attention-wo / mlp-wo, all
    other leaves replicated."""
    from repro.models import lm
    from repro.models.param import axes_tree

    def one(axes: Tuple[Optional[str], ...]) -> P:
        parts = [TP_AXIS if a in _TP_PARAM_AXES else None for a in axes]
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    return jax.tree.map(one, axes_tree(lm.lm_specs(cfg)),
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            a is None or isinstance(a, str) for a in x))


def cache_partition_specs(cache_tree):
    """PartitionSpec pytree for a (dense or paged) cache pytree: every
    kv-head-bearing axis sharded over TP, per the ``_CACHE_TP_AXIS`` table."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(cache_tree)

    def one(path, leaf) -> P:
        key = None
        for part in reversed(path):
            if hasattr(part, "key"):
                key = str(part.key)
                break
        ax = _CACHE_TP_AXIS.get(key)
        if ax is None:
            raise NotImplementedError(f"unshardable cache leaf {key!r}")
        pos = leaf.ndim + ax
        return P(*([None] * pos + [TP_AXIS]))

    return jax.tree_util.tree_unflatten(
        tdef, [one(p, l) for p, l in flat])


def _swiglu_wi_permutation(f2: int, tp: int) -> np.ndarray:
    f = f2 // 2
    fl = f // tp
    return np.concatenate([
        np.concatenate([np.arange(i * fl, (i + 1) * fl),
                        f + np.arange(i * fl, (i + 1) * fl)])
        for i in range(tp)])


def _named(specs, mesh: Mesh):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs,
                        is_leaf=lambda x: isinstance(x, P))


def param_shardings(cfg: ModelConfig, mesh: Mesh):
    """NamedSharding tree matching ``lm.lm_specs(cfg)`` — hand it to
    ``init_params`` to create weights already sharded."""
    return _named(param_partition_specs(cfg), mesh)


def _interleave_swiglu(wi, tp: int):
    """``wi[..., _swiglu_wi_permutation(f2, tp)]`` as a reshape/swap."""
    *lead, f2 = wi.shape
    w = wi.reshape(*lead, 2, tp, f2 // (2 * tp))
    return jnp.swapaxes(w, -3, -2).reshape(*lead, f2)


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    """Lay the parameter tree out for TP: permute swiglu wi columns (see
    module docstring) and device_put every leaf with its NamedSharding
    (a no-op for leaves ``init_params`` already created with
    ``param_shardings``). Returns a new global tree — pass it to the
    make_tp_* step functions."""
    tp = tp_degree(mesh)
    check_tp_supported(cfg, tp)
    specs = param_partition_specs(cfg)
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    sflat = jax.tree.leaves(specs)
    assert len(flat) == len(sflat), "param tree / spec tree mismatch"
    out = []
    for (path, leaf), spec in zip(flat, sflat):
        keys = [str(p.key) for p in path if hasattr(p, "key")]
        sharding = NamedSharding(mesh, spec)
        if cfg.act == "swiglu" and len(keys) >= 2 and \
                keys[-2] == "ffn" and keys[-1] == "wi" and tp > 1:
            # Reshape/swap instead of a gather: on a leaf that is already
            # ff-sharded it lowers to an all-to-all, not a full copy of
            # the leaf on every device.
            out.append(jax.jit(functools.partial(_interleave_swiglu, tp=tp),
                               out_shardings=sharding)(leaf))
        else:
            out.append(jax.device_put(leaf, sharding))
    return jax.tree_util.tree_unflatten(tdef, out)


def init_cache(cache_specs, mesh: Mesh):
    """Zero-filled cache pytree for ``cache_specs`` (ShapeDtypeStructs),
    created directly in its TP layout: each device allocates only its
    kv-head shard, never the whole pool."""
    return jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_specs),
        out_shardings=_named(cache_partition_specs(cache_specs), mesh))()


# ---------------------------------------------------------------------------
# Step builders — shard_map-wrapped lm entry points
# ---------------------------------------------------------------------------

def _wrap(cfg: ModelConfig, mesh: Mesh, body_of, in_specs, out_specs):
    tp = tp_degree(mesh)
    check_tp_supported(cfg, tp)
    lcfg = local_config(cfg, tp)
    sig = mesh_signature(mesh)

    def body(*args):
        with tensor_parallel(TP_AXIS, sig):
            return body_of(lcfg)(*args)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _dense_cache_specs(cfg: ModelConfig, opts) -> Any:
    from repro.models import lm
    return cache_partition_specs(
        lm.cache_specs(cfg, 1, 1, kv_dtype=opts.kv_dtype()))


def _paged_cache_specs(cfg: ModelConfig, opts) -> Any:
    from repro.models import lm
    return cache_partition_specs(
        lm.paged_cache_specs(cfg, 2, 8, kv_dtype=opts.kv_dtype()))


def make_tp_prefill(cfg: ModelConfig, mesh: Mesh, *, max_len: int, opts):
    """fn(params, tokens) → (last-pos logits (B, vocab), sharded cache)."""
    from repro.models import lm
    cspecs = _dense_cache_specs(cfg, opts)

    def body_of(lcfg):
        return lambda params, tokens: lm.prefill(
            params, lcfg, tokens, max_len=max_len, opts=opts)

    return _wrap(cfg, mesh, body_of,
                 in_specs=(param_partition_specs(cfg), P()),
                 out_specs=(P(), cspecs))


def make_tp_decode(cfg: ModelConfig, mesh: Mesh, *, opts):
    """fn(params, token, cache, pos) → (logits (B, vocab), sharded cache)."""
    from repro.models import lm
    cspecs = _dense_cache_specs(cfg, opts)

    def body_of(lcfg):
        return lambda params, token, cache, pos: lm.decode_step(
            params, lcfg, token, cache, pos, opts=opts)

    return _wrap(cfg, mesh, body_of,
                 in_specs=(param_partition_specs(cfg), P(), cspecs, P()),
                 out_specs=(P(), cspecs))


def make_tp_prefill_paged(cfg: ModelConfig, mesh: Mesh, *, opts):
    """fn(params, tokens, cache, tables, start) → (all-pos logits, cache)."""
    from repro.models import lm
    cspecs = _paged_cache_specs(cfg, opts)

    def body_of(lcfg):
        return lambda params, tokens, cache, tables, start: lm.prefill_paged(
            params, lcfg, tokens, cache, tables, start, opts)

    return _wrap(cfg, mesh, body_of,
                 in_specs=(param_partition_specs(cfg), P(), cspecs, P(), P()),
                 out_specs=(P(), cspecs))


def make_tp_decode_paged(cfg: ModelConfig, mesh: Mesh, *, opts):
    """fn(params, token, cache, tables, lens) → (logits (B, vocab), cache)."""
    from repro.models import lm
    cspecs = _paged_cache_specs(cfg, opts)

    def body_of(lcfg):
        return lambda params, token, cache, tables, lens: lm.decode_step_paged(
            params, lcfg, token, cache, tables, lens, opts)

    return _wrap(cfg, mesh, body_of,
                 in_specs=(param_partition_specs(cfg), P(), cspecs, P(), P()),
                 out_specs=(P(), cspecs))


def make_tp_verify_paged(cfg: ModelConfig, mesh: Mesh, *, opts):
    """fn(params, tokens, cache, tables, lens) → (logits (B, K, vocab),
    cache) — the speculative verify step; tokens (B, K) per-slot draft
    blocks. KV-head-sharded pools and the paged_verify kernel run per
    shard against local shapes, like the decode path."""
    from repro.models import lm
    cspecs = _paged_cache_specs(cfg, opts)

    def body_of(lcfg):
        return lambda params, tokens, cache, tables, lens: \
            lm.verify_step_paged(params, lcfg, tokens, cache, tables, lens,
                                 opts)

    return _wrap(cfg, mesh, body_of,
                 in_specs=(param_partition_specs(cfg), P(), cspecs, P(), P()),
                 out_specs=(P(), cspecs))
