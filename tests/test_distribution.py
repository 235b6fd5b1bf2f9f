"""Sharding policies, HLO analyzer, and multi-device step integration."""

import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_in_subprocess
from repro.distribution.sharding import POLICIES, spec_for
from repro.launch.hlo_analysis import analyze_hlo


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})
TP = POLICIES["train_tp"]
FSDP = POLICIES["train_fsdp_tp"]


def test_spec_basic_tp():
    assert spec_for((3072, 8192), ("d_model", "ff"), TP, MESH) == \
        P(None, "model")
    assert spec_for((200064, 3072), ("vocab", "d_model"), TP, MESH) == \
        P("model")


def test_spec_fsdp_uses_batch_domain():
    assert spec_for((3072, 8192), ("d_model", "ff"), FSDP, MESH) == \
        P("data", "model")
    # multi-pod: d_model takes (pod, data)
    assert spec_for((8192, 24576), ("d_model", "ff"), FSDP, MESH3) == \
        P(("pod", "data"), "model")


def test_spec_divisibility_fallback():
    # kv_heads=8 on a 16-way model axis → replicated
    assert spec_for((32, 128, 8, 64), ("batch", None, "kv_heads", None),
                    TP, MESH) == P("data")
    # 24 heads on 16-way → replicated (head axis), batch still sharded
    assert spec_for((32, 24, 128), ("batch", "heads", None), TP, MESH) == \
        P("data")
    # tiny batch (2) not divisible by 16 → fully replicated
    assert spec_for((2, 24, 128), ("batch", "heads", None), TP, MESH) == P()


def test_spec_pod_prefix_fallback():
    # batch 8 divisible by pod(2)·data(16)? No (32∤8) → try prefix (pod,)=2 ✓
    assert spec_for((8, 128), ("batch", None), TP, MESH3) == P("pod")


def test_spec_no_axis_reuse():
    # both dims map to model; only the first gets it
    spec = spec_for((64, 64), ("heads", "ff"), TP, MESH)
    assert spec == P("model")


def test_shard_heads_or_seq_decision():
    from repro.distribution.sharding import shard_heads_or_seq, use_sharding
    # Outside a mesh context it is a no-op (returns input unchanged).
    x = jnp.zeros((2, 24, 128, 4))
    assert shard_heads_or_seq(x, head_axis=1, seq_axis=2) is x


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_scan_trip_count_flops():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0]
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 256), jnp.float32),
                         jax.ShapeDtypeStruct((12, 256, 256),
                                              jnp.float32)).compile()
    st = analyze_hlo(c.as_text(), 1)
    expect = 2 * 128 * 256 * 256 * 12
    assert 0.95 < st.flops / expect < 1.15
    assert 12 in st.while_loops.values()
    # XLA's own analysis undercounts (documents why analyze_hlo exists)
    assert c.cost_analysis().get("flops", 0) < 0.2 * expect


def test_hlo_control_matches_cost_analysis():
    def g(a, b):
        return jnp.tanh(a @ b) @ b
    sds = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = jax.jit(g).lower(sds, sds).compile()
    st = analyze_hlo(c.as_text(), 1)
    ca = c.cost_analysis()
    assert abs(st.flops - ca["flops"]) / ca["flops"] < 0.02
    assert abs(st.bytes - ca["bytes accessed"]) / ca["bytes accessed"] < 0.1


def test_hlo_stacked_weights_charged_per_slice():
    """Scan over stacked weights must charge one layer slice per iteration,
    not the whole stack (operand-utilization semantics)."""
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        return jax.lax.scan(body, x, w)[0]
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 64), jnp.float32),
                         jax.ShapeDtypeStruct((100, 64, 64),
                                              jnp.float32)).compile()
    st = analyze_hlo(c.as_text(), 1)
    full_stack_per_iter = 100 * 100 * 64 * 64 * 4
    assert st.bytes < full_stack_per_iter * 0.2


def test_hlo_collectives_parsed_multidevice():
    out = run_in_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import _make_mesh
mesh = _make_mesh((8,), ("model",))
w_sh = NamedSharding(mesh, P("model", None))
x_sh = NamedSharding(mesh, P())
def f(x, w):
    return x @ w              # contraction over sharded dim → all-reduce
c = jax.jit(f, in_shardings=(x_sh, w_sh), out_shardings=x_sh).lower(
    jax.ShapeDtypeStruct((32, 512), jnp.float32),
    jax.ShapeDtypeStruct((512, 256), jnp.float32)).compile()
st = analyze_hlo(c.as_text(), 8)
assert st.wire_bytes > 0, st
assert any(k in st.op_bytes for k in ("all-reduce", "reduce-scatter")), st.op_bytes
print("OK", sorted(st.op_bytes))
""", devices=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# end-to-end sharded train step (8 devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_train_step_runs_and_improves():
    out = run_in_subprocess("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch import steps as S
from repro.models import lm
from repro.models.param import init_params
cfg = get_config("olmoe-1b-7b", smoke=True)
from repro.launch.mesh import _make_mesh
mesh = _make_mesh((2, 4), ("data", "model"))
scfg = S.StepConfig(micro_batches=2)
psh = S.param_tree_shardings(cfg, mesh, scfg.policy)
params = jax.device_put(init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg)), psh)
osh = S.opt_state_shardings(cfg, scfg, mesh)
opt = jax.device_put(S.init_opt_state(cfg, scfg, params), osh)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": toks}
bsh = S.batch_shardings(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
                        mesh, S.POLICIES[scfg.policy])
batch = jax.device_put(batch, bsh)
step = jax.jit(S.make_train_step(cfg, scfg, mesh),
               in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None))
losses = []
p, o = params, opt
for i in range(8):
    p, o, m = step(p, o, batch)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("OK", losses[0], "->", losses[-1])
""", devices=8, timeout=600)
    assert "OK" in out
