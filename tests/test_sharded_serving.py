"""Tensor-parallel sharded serving (distribution/tp.py) + shard-aware
autotuning (DESIGN.md §11).

The contract under test: TP=2 and TP=4 decode are token-for-token the
single-device dense path, the tuner keys sharded kernel launches on
(local shapes, mesh signature) — distinct from unsharded keys, with no
fallback to global-shape entries — and the paged ServingEngine serves
identically at tp>1. Multi-device pieces run in subprocesses with forced
host devices (jax pins the device count at first init)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import run_in_subprocess
from repro.core.cache import cache_key
from repro.core.config_space import ConfigSpace, Param, TuningContext
from repro.core.hardware import get_chip
from repro.distribution import tp as tp_lib
from repro.distribution.sharding import (
    current_mesh_signature, tensor_parallel, tp_psum,
)
from repro.models.config import ModelConfig


def _tiny_cfg(**kw):
    base = dict(name="tp-t", family="dense", n_layers=2, d_model=32,
                n_heads=8, n_kv_heads=4, head_dim=8, d_ff=64,
                vocab_size=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Mesh-signature cache keys (no devices needed)
# ---------------------------------------------------------------------------

def test_mesh_signature_keys_distinct_from_unsharded():
    """Same kernel + same (local) shapes: the sharded scenario must be a
    different cache key than the unsharded one, and TP degrees must not
    share keys either."""
    space = ConfigSpace("s", [Param("block_kv", (128, 256))])
    chip = get_chip("tpu_v5e")
    shapes = {"q": (16, 8, 128), "k": (16, 2, 32768, 128)}
    plain = TuningContext(chip=chip, shapes=shapes)
    tp2 = TuningContext(chip=chip, shapes=shapes, mesh={"model": 2})
    tp4 = TuningContext(chip=chip, shapes=shapes, mesh={"model": 4})
    sigs = {plain.signature(), tp2.signature(), tp4.signature()}
    assert len(sigs) == 3
    keys = {cache_key("k", 1, space, c) for c in (plain, tp2, tp4)}
    assert len(keys) == 3
    assert '"mesh": {"model": 2}' in tp2.signature()
    # Unsharded signatures omit the field entirely: byte-identical to
    # pre-mesh signatures, so previously persisted entries stay hittable.
    assert "mesh" not in plain.signature()


def test_cache_refuses_cross_mesh_reuse(tuner):
    """An entry tuned for the unsharded scenario is never served to the
    mesh-signature scenario (and vice versa) — the 'no fallback to
    global-shape entries' guarantee at the cache layer."""
    from repro.core.tuner import TunableKernel

    space = ConfigSpace("s", [Param("a", (1, 2, 3))])
    kern = TunableKernel(
        name="k", space=space,
        workload_fn=lambda cfg, ctx: _unit_workload(cfg))
    chip = get_chip("tpu_v5e")
    shapes = {"x": (8, 8)}
    plain = TuningContext(chip=chip, shapes=shapes)
    tp2 = TuningContext(chip=chip, shapes=shapes, mesh={"model": 2})
    tuner.tune(kern, plain)
    assert tuner.cache.get("k", 1, space, plain) is not None
    assert tuner.cache.get("k", 1, space, tp2) is None
    tuner.best_config(kern, tp2)               # miss → tunes the TP scenario
    stats = tuner.stats()
    assert stats["misses"] == 1 and stats["tunes"] == 2


def _unit_workload(cfg):
    from repro.core.costmodel import KernelWorkload
    return KernelWorkload(flops=1e6 * cfg["a"], hbm_bytes=1e6,
                          grid_steps=1, vmem_bytes=1024)


def test_mesh_signature_context():
    """ops.py reads the tensor_parallel contextvar; outside it the
    signature is empty, inside it is the mesh's non-trivial axes."""
    assert current_mesh_signature() == {}
    with tensor_parallel("model", {"model": 4}):
        assert current_mesh_signature() == {"model": 4}
    assert current_mesh_signature() == {}
    # tp_psum is the identity outside a TP context (single-device path).
    x = jnp.ones((2, 2))
    assert tp_psum(x) is x


# ---------------------------------------------------------------------------
# Local-config / param-layout plumbing (no devices needed)
# ---------------------------------------------------------------------------

def test_local_config_divides_heads_and_ff():
    cfg = _tiny_cfg()
    lcfg = tp_lib.local_config(cfg, 4)
    assert (lcfg.n_heads, lcfg.n_kv_heads, lcfg.d_ff) == (2, 1, 16)
    assert lcfg.head_dim == cfg.head_dim and lcfg.d_model == cfg.d_model
    assert tp_lib.local_config(cfg, 1) is cfg


def test_tp_rejects_unsupported():
    with pytest.raises(ValueError, match="not divisible"):
        tp_lib.check_tp_supported(_tiny_cfg(n_kv_heads=2), 4)
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        tp_lib.check_tp_supported(_tiny_cfg(window=8), 2)
    from repro.models.config import MLAConfig
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        tp_lib.check_tp_supported(_tiny_cfg(mla=MLAConfig()), 2)


def test_param_partition_specs_column_row():
    from jax.sharding import PartitionSpec as P
    specs = tp_lib.param_partition_specs(_tiny_cfg())
    layer = specs["u0"]["l0"]
    # stacked layer params carry a leading (reps) replicated dim
    assert layer["mix"]["wq"] == P(None, None, "model")      # column
    assert layer["mix"]["wo"] == P(None, "model")            # row
    assert layer["ffn"]["wi"] == P(None, None, "model")      # column
    assert layer["ffn"]["wo"] == P(None, "model")            # row
    assert layer["ln1"]["w"] == P()                          # replicated
    assert specs["embed"]["tok"] == P()                      # replicated


def test_swiglu_wi_permutation_is_shardwise_gate_up():
    import numpy as np
    f2, tp = 16, 4
    perm = tp_lib._swiglu_wi_permutation(f2, tp)
    f, fl = f2 // 2, f2 // 2 // tp
    for i in range(tp):
        shard = perm[i * 2 * fl:(i + 1) * 2 * fl]
        # each shard's slice is [its gate cols | its up cols]
        assert list(shard[:fl]) == list(range(i * fl, (i + 1) * fl))
        assert list(shard[fl:]) == list(range(f + i * fl, f + (i + 1) * fl))
    assert sorted(perm) == list(range(f2))


def test_interleave_swiglu_matches_permutation():
    import numpy as np
    f2, tp = 16, 4
    wi = np.arange(3 * 5 * f2, dtype=np.float32).reshape(3, 5, f2)
    np.testing.assert_array_equal(
        np.asarray(tp_lib._interleave_swiglu(jnp.asarray(wi), tp)),
        wi[..., tp_lib._swiglu_wi_permutation(f2, tp)])


# ---------------------------------------------------------------------------
# Token-for-token equality + mesh-keyed tuning (8 forced host devices)
# ---------------------------------------------------------------------------

def test_tp_decode_token_for_token_and_mesh_keyed_cache():
    """TP=2 and TP=4 dense decode (registry pallas decode kernels on the
    hot path) produce exactly the single-device greedy tokens; the tuner's
    entries for the sharded launches live under mesh-signature keys, the
    second trace hits them, and the pre-seeded global-shape entry is never
    served to the sharded scenario."""
    out = run_in_subprocess("""
import os, tempfile
os.environ["REPRO_TUNING_CACHE"] = tempfile.mkdtemp()
import jax, jax.numpy as jnp, numpy as np, json
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.param import init_params
from repro.distribution import tp as tp_lib
from repro.core.tuner import default_tuner

cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=8, n_kv_heads=4, head_dim=8, d_ff=64,
                  vocab_size=128, dtype="float32")
params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
rng = np.random.default_rng(0)
prompt = rng.integers(1, cfg.vocab_size, 7).astype(np.int32)
P_, G = len(prompt), 6
tuner = default_tuner()

def greedy(prefill, decode, params):
    lg, cache = prefill(params, jnp.asarray(prompt[None], jnp.int32))
    out = [int(jnp.argmax(lg[0]))]
    for i in range(G - 1):
        lg, cache = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                           cache, jnp.int32(P_ + i))
        out.append(int(jnp.argmax(lg[0])))
    return out

# single-device dense reference (einsum path, no mesh)
opts_ref = lm.ForwardOpts(attn_impl="full", decode_impl="full")
want = greedy(
    lambda p, t: lm.prefill(p, cfg, t, max_len=P_ + G, opts=opts_ref),
    lambda p, t, c, i: lm.decode_step(p, cfg, t, c, i, opts=opts_ref),
    params)

# Pre-seed the UNSHARDED pallas-decode scenario: the sharded runs below
# must not be served from it (different shapes AND different mesh key).
kv = jnp.zeros((1, P_ + G, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
from repro.kernels import ops as kops
kops.ragged_decode(jnp.zeros((1, cfg.n_heads, cfg.head_dim), jnp.float32),
                   jnp.moveaxis(kv, 1, 2), jnp.moveaxis(kv, 1, 2),
                   kv_len=jnp.ones((1,), jnp.int32))
seeded = dict(tuner.stats())

opts_p = lm.ForwardOpts(attn_impl="full")
opts_d = lm.ForwardOpts(decode_impl="pallas")
for tp in (2, 4):
    mesh = tp_lib.make_tp_mesh(tp)
    sp = tp_lib.shard_params(params, cfg, mesh)
    pre = jax.jit(tp_lib.make_tp_prefill(cfg, mesh, max_len=P_ + G, opts=opts_p))
    dec = jax.jit(tp_lib.make_tp_decode(cfg, mesh, opts=opts_d))
    got = greedy(pre, dec, sp)
    assert got == want, (tp, got, want)
    # Re-tracing the decode step must HIT the mesh-keyed entry.
    before = tuner.stats()["per_kernel"]["gqa_decode_ragged"]["hits"]
    dec2 = jax.jit(tp_lib.make_tp_decode(cfg, mesh, opts=opts_d))
    lg, cache = pre(sp, jnp.asarray(prompt[None], jnp.int32))
    dec2(sp, jnp.asarray([[int(jnp.argmax(lg[0]))]], jnp.int32), cache,
         jnp.int32(P_))
    after = tuner.stats()["per_kernel"]["gqa_decode_ragged"]
    assert after["hits"] > before, after

# Every sharded launch was its own scenario: one tune per TP degree on
# top of the seeded unsharded one, no reuse of the global-shape entry.
stats = tuner.stats()["per_kernel"]["gqa_decode_ragged"]
assert stats["tunes"] == seeded["per_kernel"]["gqa_decode_ragged"]["tunes"] + 2, stats
# The process-local DB (not the shipped overlay) holds exactly one
# mesh-keyed entry per TP degree, at the per-shard LOCAL head counts.
local_keys = {}
for k in tuner.cache._db:
    kd = json.loads(k)
    if kd["kernel"] != "gqa_decode_ragged":
        continue
    ctx = json.loads(kd["ctx"])
    if ctx.get("mesh"):
        local_keys[tuple(ctx["shapes"]["q"])] = ctx["mesh"]
assert local_keys == {(1, 4, 8): {"model": 2}, (1, 2, 8): {"model": 4}}, \
    local_keys
print("OK", want)
""", devices=8, timeout=900)
    assert "OK" in out


def test_tp_paged_engine_matches_single_device_engine():
    """The continuous-batching ServingEngine at tp=2 generates exactly the
    tokens the tp=1 engine generates on the same trace, with the pool
    whole afterwards."""
    out = run_in_subprocess("""
import os, tempfile, copy
os.environ["REPRO_TUNING_CACHE"] = tempfile.mkdtemp()
import jax, numpy as np
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.param import init_params
from repro.serving import Request, ServingEngine

cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=8, n_kv_heads=4, head_dim=8, d_ff=64,
                  vocab_size=128, dtype="float32")
params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
rng = np.random.default_rng(42)
reqs = [Request(rid=i,
                prompt=rng.integers(1, cfg.vocab_size, int(p)).astype(np.int32),
                max_new_tokens=int(g))
        for i, (p, g) in enumerate(zip(rng.integers(2, 10, 4),
                                       rng.integers(1, 5, 4)))]
kw = dict(num_pages=24, page_size=8, max_batch=3, max_seq_len=24,
          prefill_chunk=4)
e1 = ServingEngine(cfg, params, **kw)
e1.run(copy.deepcopy(reqs))
e2 = ServingEngine(cfg, params, tp=2, **kw)
e2.run(copy.deepcopy(reqs))
t1 = {r.rid: r.tokens for r in e1.scheduler.finished}
t2 = {r.rid: r.tokens for r in e2.scheduler.finished}
assert t1 == t2, (t1, t2)
e2.scheduler.check_invariants()
assert e2.pool.num_allocated == 0
print("OK", sum(map(len, t2.values())), "tokens")
""", devices=8, timeout=900)
    assert "OK" in out


def test_tp_engine_gates_weight_quant():
    cfg = _tiny_cfg()
    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    if len(jax.devices()) < 2:
        # tp=1 host: the quant gate fires before mesh construction only if
        # tp>1 — exercise the error path via make_tp_mesh's device check.
        with pytest.raises(ValueError, match="device"):
            ServingEngine(cfg, params, num_pages=8, page_size=8, max_batch=1,
                          max_seq_len=16, prefill_chunk=4, tp=2, quant="kv8")
    else:
        with pytest.raises(NotImplementedError, match="weight quantization"):
            ServingEngine(cfg, params, num_pages=8, page_size=8, max_batch=1,
                          max_seq_len=16, prefill_chunk=4, tp=2, quant="w8a8")
