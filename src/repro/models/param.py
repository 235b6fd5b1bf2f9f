"""Parameter specification trees.

A model declares its parameters once, as a pytree of ``ParamSpec``s; from
that single source we derive
  * ``init_params``   — materialized random weights (CPU smoke tests,
                        examples, real training),
  * ``shape_tree``    — ShapeDtypeStructs for the 512-device dry-run
                        (no allocation, per the brief),
  * ``axes_tree``     — logical sharding axes per leaf, consumed by
                        distribution/sharding.py to build PartitionSpecs.

Logical axis names used across the zoo:
    "d_model", "ff", "heads", "kv_heads", "vocab", "experts",
    "ssm_heads", "conv", None (replicated dims)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"          # normal | zeros | ones
    scale: Optional[float] = None  # default: 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_spec(spec: ParamSpec, repeats: int) -> ParamSpec:
    """Add a leading layer-stacking dim (for scan-over-layers units)."""
    return ParamSpec((repeats,) + tuple(spec.shape), (None,) + tuple(spec.axes),
                     spec.dtype, spec.init, spec.scale)


def stack_tree(tree, repeats: int):
    return jax.tree.map(lambda s: stack_spec(s, repeats), tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _init_leaf(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(
        spec.dtype)


_init_leaf_jit = jax.jit(_init_leaf, static_argnames="spec")


def _draw_leaf(key, spec: ParamSpec, sharding=None):
    if sharding is not None:
        return jax.jit(_init_leaf, static_argnames="spec",
                       out_shardings=sharding)(key, spec=spec)
    if jnp.dtype(spec.dtype) == jnp.float32:
        return _init_leaf(key, spec)      # the draw already is the leaf
    # One program per narrower leaf fuses the float32 draw into the cast:
    # eagerly, a (32, 3072, 16384) bf16 leaf would first exist as 6.4 GB
    # of float32.
    return _init_leaf_jit(key, spec=spec)


def init_params(rng, specs, shardings=None):
    """Random weights for ``specs``. With a ``shardings`` tree (one
    ``jax.sharding.Sharding`` per leaf) every leaf is created already laid
    out, never whole on one device first."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    outs = (jax.tree.leaves(shardings) if shardings is not None
            else [None] * len(leaves))
    assert len(outs) == len(leaves), "param tree / sharding tree mismatch"
    return jax.tree.unflatten(
        treedef, [_draw_leaf(k, s, o) for k, s, o in zip(keys, leaves, outs)])


def shape_tree(specs):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def axes_tree(specs):
    return jax.tree.map(lambda s: tuple(s.axes), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(math.prod(s.shape)) for s in leaves)


def param_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(math.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in leaves)
