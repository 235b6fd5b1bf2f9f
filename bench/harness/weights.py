"""Seeded weights, drawn the same way for the program and the reference.

Every leaf is addressed by its name within a layer (``mix/wq``) and the
layer's index, so the reference can draw one layer at a time and get the
very values the program serves, without taking anything the program made.

  normal leaves:  N(0, 1) * scale, drawn in float32, rounded to the leaf's
                  dtype; scale 1 / sqrt(fan_in) (rows of a matrix, the
                  length of a vector), 1 for an untied token embedding
  tied embedding: 1 / sqrt(d_model), the scale of the output head it also
                  serves as; at scale 1 a token's own row would outscore
                  every other by about d_model and make each token predict
                  itself
  ones, zeros:    where the leaf's kind says so (the program's
                  ``ParamSpec.init``: norm weights, biases); a reference
                  states the kind of each leaf it draws
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any non-negative seed, 64 bits and more included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def leaf_init(name: str, shape, tied: bool = False,
              init: str = "normal") -> tuple:
    """(kind, scale) of a leaf of kind ``init`` (normal, ones or zeros).
    ``tied``: the token embedding is also the output head."""
    if init != "normal":
        return init, 1.0
    if name == "embed/tok":
        return "normal", float(shape[-1]) ** -0.5 if tied else 1.0
    fan_in = shape[-2] if len(shape) > 1 else shape[-1]
    return "normal", float(fan_in) ** -0.5


def draw(key, name: str, layer, shape, dtype, tied: bool = False,
         init: str = "normal"):
    """Leaf ``name`` of layer ``layer`` (0 for leaves outside the layers)."""
    kind, scale = leaf_init(name, shape, tied, init)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind != "normal":
        raise ValueError(f"{name}: unknown kind {kind!r}")
    k = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(name.encode()) & 0x7FFFFFFF), layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
