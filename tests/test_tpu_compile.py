"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

Each test compiles one kernel with ``interpret=False`` for a described
v5e chip (no chip attached) at phi3-mini-3.8b's published widths — 32
heads of 96, bf16 — and asserts that the program holds a Mosaic kernel
(``tpu_custom_call``). This is what the TPU compiler would refuse that
interpret mode accepts: blocks not aligned to the (8, 128) tiling, and
more VMEM than a kernel may use.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load libtpu, and xdist workers import every
test file. Keep all such compiles in this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.gen_shipped_db import (
    SHIP_DTYPE, paged_deployment_shapes,
)
from repro.core import AnalyticalMeasure, Autotuner, TuningCache, get_chip
from repro.core.config_space import TuningContext
from repro.core.portfolio import Portfolio
from repro.core.tuner import SHIPPED_DB
from repro.kernels.paged_decode import paged_decode
from repro.kernels.paged_verify import paged_verify
from repro.kernels.rms_norm import rms_norm

PHI3 = get_config("phi3-mini-3.8b")
BATCH = 8                 # concurrent sequences, as chip_smoke.py serves
MAX_SEQ = 672             # 512 prompt + 32 generated + a 128-token chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def deployed(tmp_path_factory):
    """The paged_decode / paged_verify configs the shipped DB and the
    portfolio pick for phi3-mini's deployment scenario, per dtype."""
    tuner = Autotuner(
        cache=TuningCache(cache_dir=str(tmp_path_factory.mktemp("tuning")),
                          overlay_path=SHIPPED_DB),
        backend=AnalyticalMeasure(get_chip("tpu_v5e")),
        portfolio=Portfolio.load_shipped(), on_miss="error")

    def pick(kernel, dtype):
        ctx = TuningContext(chip=get_chip("tpu_v5e"),
                            shapes=paged_deployment_shapes(PHI3),
                            dtype=dtype)
        return tuner.best_config(kernel, ctx)

    return pick


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


def _pool_args(one_chip, cfg, dtype):
    """(k_pages, v_pages, block_tables, kv_len[, scales]) shapes of a pool
    holding BATCH sequences of MAX_SEQ tokens at the config's page size."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    ps = cfg["page_size"]
    max_pages = -(-MAX_SEQ // ps)
    pool = (PHI3.n_kv_heads, 1 + BATCH * max_pages, ps, PHI3.head_dim)
    args = [sds(pool, dtype), sds(pool, dtype),
            sds((BATCH, max_pages), jnp.int32), sds((BATCH,), jnp.int32)]
    scales = {}
    if dtype == jnp.int8:
        scales = {k: sds(pool[:-1], jnp.float32)
                  for k in ("k_scales", "v_scales")}
    return args, scales


@pytest.mark.parametrize("dtype", [SHIP_DTYPE, "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, deployed, dtype):
    cfg = deployed("paged_decode", dtype)
    pool_dtype = jnp.int8 if dtype == "int8" else jnp.bfloat16
    args, scales = _pool_args(one_chip, cfg, pool_dtype)
    q = jax.ShapeDtypeStruct((BATCH, PHI3.n_heads, PHI3.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(paged_decode, block_kv=cfg["block_kv"],
                           pack_gqa=cfg["pack_gqa"], interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, *args, **scales)


def test_paged_verify_compiles_for_v5e(one_chip, deployed):
    cfg = deployed("paged_verify", SHIP_DTYPE)
    args, _ = _pool_args(one_chip, cfg, jnp.bfloat16)
    q = jax.ShapeDtypeStruct(
        (BATCH, cfg["draft_k"], PHI3.n_heads, PHI3.head_dim), jnp.bfloat16,
        sharding=one_chip)
    fn = functools.partial(paged_verify, block_kv=cfg["block_kv"],
                           pack_gqa=cfg["pack_gqa"], interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, *args)


def test_rms_norm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((BATCH * 128, PHI3.d_model), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((PHI3.d_model,), jnp.bfloat16,
                             sharding=one_chip)
    fn = functools.partial(rms_norm, block_rows=128, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, w)
