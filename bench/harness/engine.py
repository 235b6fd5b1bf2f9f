"""The system under test, built as a deployment builds it.

This is the one module of the harness that imports the program. It turns
a configuration file into ``repro``'s model config, draws the weights on
the device in one jitted call, and builds ``repro.serving.ServingEngine``
as ``repro.launch.serve.serve_paged`` does: page size from the tuner's
deployment lookup, donated step programs, the prefix cache as the
configuration says.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from harness import weights

# What the program implements, and so what a configuration must state.
PROGRAM_FIXED = {"rms_norm_eps": 1e-06, "partial_rotary_factor": 1.0,
                 "rope_scaling": None, "hidden_act": "silu"}


def model_config(config: Dict):
    """``repro.models.config.ModelConfig`` of a configuration file."""
    from repro.models.config import ModelConfig
    for k, v in PROGRAM_FIXED.items():
        if config.get(k, v) != v:
            raise ValueError(f"{config['name']}: {k}={config[k]!r}; the "
                             f"program implements {v!r}")
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=d, n_heads=hq,
        n_kv_heads=config["num_key_value_heads"], head_dim=d // hq,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        dtype=config["deployment"]["dtype"])


def _leaf_name(path) -> tuple:
    """(name within a layer or of a global leaf, stacked?) of a tree path
    such as u0/l0/mix/wq."""
    keys = [p.key for p in path]
    if keys[0].startswith("u"):
        return "/".join(keys[2:]), keys[0], int(keys[1][1:])
    return "/".join(keys), None, None


def draw_params(cfg, seed: int):
    """The program's parameter tree, drawn from ``seed`` in one jitted
    call: the layers of a stacked unit are drawn in a loop on the device,
    each leaf in float32 and rounded to its served dtype."""
    from repro.models import lm
    from repro.models.param import ParamSpec
    specs = lm.lm_specs(cfg)
    plan = cfg.scan_plan()
    first = {}                     # unit -> index of its first layer
    n = 0
    for ui, (unit, reps) in enumerate(plan):
        first[f"u{ui}"] = (n, len(unit), reps)
        n += len(unit) * reps

    def leaf(key, path, spec):
        name, unit, pos = _leaf_name(path)
        if unit is None:
            return weights.draw(key, name, 0, spec.shape, spec.dtype,
                                tied=cfg.tie_embeddings)
        start, width, reps = first[unit]
        if reps == 1:
            return weights.draw(key, name, start + pos, spec.shape,
                                spec.dtype)
        layers = start + pos + width * jnp.arange(reps)
        return jax.lax.map(
            lambda l: weights.draw(key, name, l, spec.shape[1:],
                                   spec.dtype), layers)

    @jax.jit
    def draw_all(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: leaf(key, p, s), specs,
            is_leaf=lambda x: isinstance(x, ParamSpec))

    return draw_all(weights.base_key(seed))


def deployment_page_size(cfg) -> int:
    """The pool's page size from the tuner's deployment-level
    ``paged_decode`` entry, as ``serve_paged`` looks it up."""
    from repro.configs.gen_shipped_db import (
        SHIP_DTYPE, paged_deployment_shapes, tp_mesh_signature)
    from repro.core.config_space import TuningContext
    from repro.core.tuner import default_tuner
    tuner = default_tuner()
    chip = getattr(tuner.backend, "chip", None) or getattr(
        getattr(tuner.backend, "analytical", None), "chip", None)
    ctx = TuningContext(chip=chip, shapes=paged_deployment_shapes(cfg, tp=1),
                        dtype=SHIP_DTYPE, mesh=tp_mesh_signature(1))
    return int(tuner.best_config("paged_decode", ctx)["page_size"])


@dataclasses.dataclass
class Built:
    engine: object
    cfg: object
    page_size: int
    num_pages: int


def build(config: Dict, seed: int) -> Built:
    from repro.serving import ServingEngine
    dep = config["deployment"]
    cfg = model_config(config)
    page_size = deployment_page_size(cfg)
    num_pages = dep["kv_pool_tokens"] // page_size
    params = draw_params(cfg, seed)
    engine = ServingEngine(
        cfg, params, num_pages=num_pages, page_size=page_size,
        max_batch=dep["max_batch"], max_seq_len=dep["max_seq_len"],
        prefill_chunk=dep["prefill_chunk"],
        prefix_cache=bool(dep["prefix_cache"]))
    return Built(engine, cfg, page_size, num_pages)


def warm_up(engine) -> None:
    """Compile and run the cell's two step programs once at the shapes the
    window uses, on the scratch page only: the prefill chunk (1, C) and
    the decode step at max_batch. Also the token read after prefill."""
    sched = engine.scheduler
    B, W, C = sched.max_batch, sched.max_pages, sched.prefill_chunk
    i32 = jnp.int32
    ptoks, pok, engine.cache = engine._prefill_fn(
        engine.params, jnp.zeros((1, C), i32), engine.cache,
        jnp.zeros((1, W), i32), jnp.asarray([0], i32))   # as step() makes it
    bool(jax.device_get(pok)[0, C - 1])
    int(ptoks[0, C - 1])
    dtoks, dok, engine.cache = engine._decode_fn(
        engine.params, jnp.zeros((B, 1), i32), engine.cache,
        jnp.zeros((B, W), i32), jnp.zeros((B,), i32),
        jnp.ones((B, 1), jnp.float32))
    jax.device_get((dtoks, dok))
    jax.block_until_ready(engine.cache)
