"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

Each kernel test compiles one kernel with ``interpret=False`` for a
described v5e chip (no chip attached) at phi3-mini-3.8b's published
widths — 32 heads of 96, bf16 — and asserts that the program holds a
Mosaic kernel (``tpu_custom_call``). This is what the TPU compiler would
refuse that interpret mode accepts: blocks not aligned to the (8, 128)
tiling, and more VMEM than a kernel may use.

The step-program tests compile the serving engine's decode and prefill
programs, at phi3-mini's and phi4-mini's widths and a reduced depth, and
read the compiled HLO: the page pool must be written in place and never
sliced, copied or relaid out whole.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load libtpu, and xdist workers import every
test file. Keep all such compiles in this one file.
"""

import dataclasses
import functools
import os
import re

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
from repro.core import tuner as tuner_lib
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


# ---------------------------------------------------------------------------
# The engine's step programs: the page pool stays in place
# ---------------------------------------------------------------------------

STEP_LAYERS = 4           # scanned layers: depth is cut, widths are not
STEP_PAGES = 40           # pages of 256 tokens, phi3-mini's served pool
STEP_PAGE = 256
STEP_TABLE = 17           # pages a 4352-token sequence spans
STEP_CHUNK = 256
STEP_BATCH = 12

# Ops that only carry a pool through the program, and the one that may
# change it: an in-place dynamic-update-slice.
_CARRIERS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "call", "conditional", "dynamic-update-slice"}


def _pool_ops(hlo: str, hkv: int, pages: int):
    """(op, result type) of each instruction of a compiled HLO module whose
    result holds a page pool — its (Hkv, P) dims side by side — other than
    the carriers and fusions whose root is a dynamic-update-slice."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    inst = re.compile(r"\s*(?:ROOT )?%\S+ = (\([^=]*?\)|\S+) ([\w-]+)\(")
    root_op = {}
    for cname, lines in comps.items():
        for line in lines:
            m = inst.match(line)
            if m and line.lstrip().startswith("ROOT"):
                root_op[cname] = m.group(2)
    pool = re.compile(rf"\[(?:\d+,)*{hkv},{pages}(?:,\d+)*\]")
    found = []
    for lines in comps.values():
        for line in lines:
            m = inst.match(line)
            if not m or not pool.search(m.group(1)) or m.group(2) in _CARRIERS:
                continue
            if m.group(2) == "fusion":
                callee = re.search(r"calls=%([\w.-]+)", line).group(1)
                if root_op.get(callee) == "dynamic-update-slice":
                    continue
            found.append((m.group(2), m.group(1)[:120]))
    return found


@pytest.fixture
def tpu_tracing(monkeypatch, tmp_path):
    """Trace programs as the chip would: Pallas kernels lowered by Mosaic
    (the process backend is the CPU) and dispatched through a v5e tuner
    over the shipped DB, which tunes a missing scenario analytically."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuner_lib, "_DEFAULT", Autotuner(
        cache=TuningCache(cache_dir=str(tmp_path), overlay_path=SHIPPED_DB),
        backend=AnalyticalMeasure(get_chip("tpu_v5e")), on_miss="tune"))


def _compiled_step(one_chip, cfg, program, pages):
    from repro.models import lm
    from repro.serving.scheduler import step_programs

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    opts = lm.ForwardOpts(decode_impl="paged")
    params = on_chip(jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0),
                                                    cfg)))
    cache = on_chip(lm.paged_cache_specs(cfg, pages, STEP_PAGE))
    prefill, decode, _ = (jax.jit(fn, donate_argnums=2) if fn else None
                          for fn in step_programs(cfg, opts))
    if program == "prefill":
        return prefill.lower(params, arg((1, STEP_CHUNK)), cache,
                             arg((1, STEP_TABLE)), arg((1,))).compile()
    B = STEP_BATCH
    return decode.lower(params, arg((B, 1)), cache, arg((B, STEP_TABLE)),
                        arg((B,)), arg((B, 1), jnp.float32)).compile()


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "phi4-mini-3.8b"])
def test_step_program_keeps_pool_in_place_for_v5e(one_chip, tpu_tracing,
                                                  arch, program):
    """Head dims 96 (a pool of that width would have its page slots minor
    by default) and 128: no op but an in-place dynamic-update-slice yields
    a layer's pool or the stacked pool, and no temporary grows with the
    pool. The bound is one layer's K pool on the part of the temporary
    that a 2-page pool does not have: the prefill's dense attention keeps
    its chunk's scores over the whole table (143 MB at phi3's widths),
    whatever the pool."""
    from repro.models import lm
    cfg = dataclasses.replace(get_config(arch), n_layers=STEP_LAYERS)
    compiled = _compiled_step(one_chip, cfg, program, STEP_PAGES)
    assert "tpu_custom_call" in compiled.as_text()
    assert _pool_ops(compiled.as_text(), cfg.n_kv_heads, STEP_PAGES) == []
    k_pool = lm.paged_cache_specs(cfg, STEP_PAGES, STEP_PAGE)["u0"]["l0"][
        "self"]["k_pages"]
    layer_k_pool = k_pool.size // STEP_LAYERS * k_pool.dtype.itemsize
    small = _compiled_step(one_chip, cfg, program, 2)
    grown = (compiled.memory_analysis().temp_size_in_bytes
             - small.memory_analysis().temp_size_in_bytes)
    assert grown < layer_k_pool
