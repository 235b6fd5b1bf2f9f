"""Registry-driven oracle conformance: every registered kernel's entry
point must match its ref.py oracle on all host-scale bench cases across
several sampled configs.

Before this sweep, oracle coverage was per-kernel and ad-hoc (each kernel
hand-rolled its own operand plumbing in its own test file). The registry's
``operands`` hook makes conformance declarative: a new kernel that
registers (reference, entry_point, operands) is swept here with zero new
test code."""

import numpy as np
import pytest

from repro.core import get_chip
from repro.kernels.registry import list_kernels

CHIP = get_chip("tpu_v5e")

CONFORMANCE = [
    (spec, case)
    for spec in list_kernels()
    if spec.reference is not None and spec.entry_point is not None
    and spec.operands is not None
    for case in spec.cases(scale="host")
]


def _sampled_configs(spec, ctx, n=3):
    """A spread sample of the valid configs (first / middle / last after
    constraint filtering) — cheap but layout-diverse."""
    cfgs = spec.space.valid_configs(ctx)
    assert cfgs, f"{spec.name}: no valid config for {ctx.signature()}"
    step = max(1, len(cfgs) // n)
    return cfgs[::step][:n]


def _tol(dtype):
    """Per-precision-family conformance tolerance. Int8 kernels and their
    oracles dequantize the SAME integer values, so they agree to float
    rounding — but the kernel fuses scales post-accumulation (exact int32
    path) while the oracle dequantizes first (f32 rounding per element),
    a legitimately different rounding order that needs more headroom than
    a pure-f32 kernel and less than bf16 storage error."""
    if dtype == "bfloat16":
        return 2e-2
    if dtype == "int8":
        return 2e-3
    return 1e-4


@pytest.mark.parametrize(
    "spec,case", CONFORMANCE,
    ids=[f"{s.name}/{c.label}" for s, c in CONFORMANCE])
def test_entry_point_matches_oracle(spec, case):
    ctx = case.context(CHIP)
    first = None
    for cfg in _sampled_configs(spec, ctx):
        args, kwargs = spec.operands(ctx, cfg)
        got = spec.entry_point(*args, config=cfg, **kwargs)
        ref_out = spec.reference(*args, **kwargs)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref_out, np.float32),
            atol=_tol(case.dtype), rtol=1e-4,
            err_msg=f"{spec.name}/{case.label} config {cfg}")
        if first is None:
            first = (args, np.asarray(ref_out, np.float32))
        elif all(a is b for a, b in zip(args, first[0])):
            # Identical (memoized) operands across configs: the oracle is
            # config-free, so its output must be bit-stable. Kernels whose
            # operand *layout* is config-dependent (paged pools relayout
            # per page_size) rebuild args and legitimately skip this.
            np.testing.assert_array_equal(
                np.asarray(ref_out, np.float32), first[1],
                err_msg=f"{spec.name}: oracle output varies with config")


def test_every_swept_kernel_has_host_case():
    """A kernel with an oracle but no host-scale case silently escapes the
    sweep — fail loudly instead."""
    for spec in list_kernels():
        if spec.reference is not None and spec.operands is not None:
            assert spec.cases(scale="host"), \
                f"{spec.name} has an oracle but no host bench case"


def test_decode_family_is_fully_swept():
    """Every serving-path kernel must be in the conformance sweep: oracle,
    entry point, and operand builder all declared."""
    swept = {s.name for s, _ in CONFORMANCE}
    for spec in list_kernels(scenario="decode"):
        assert spec.name in swept, \
            f"decode kernel {spec.name} missing oracle/entry/operands"


def test_quant_family_is_fully_swept_at_int8_cases():
    """Every int8-precision kernel is in the sweep AND contributes at
    least one int8-dtype host case (so the int8 tolerance path actually
    runs — a quant kernel swept only at float dtypes would silently test
    nothing quantized)."""
    quant = list_kernels(precision="int8")
    assert {s.name for s in quant} >= {"matmul_w8a8", "gqa_decode_kv8"}
    swept = {(s.name, c.dtype) for s, c in CONFORMANCE}
    for spec in quant:
        assert (spec.name, "int8") in swept, \
            f"{spec.name} has no int8 host case in the conformance sweep"
    # paged_decode serves both families: float pools and int8 (kv8) pools
    # must both conform.
    assert ("paged_decode", "int8") in swept
    assert ("paged_decode", "float32") in swept


# ---------------------------------------------------------------------------
# Stacked pools: the serving model hands the paged kernels every layer's
# pool in one buffer (L, Hkv, P, page_size, D) and the layer to read.
# ---------------------------------------------------------------------------

def _stacked_pool(quantized, L=3, Hkv=2, P=9, ps=8, D=16):
    """A stacked pool as the model stores it: head dim zero-padded to
    whole lanes, scales (L, Hkv, P, 1, page_size)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_decode import pool_head_dim
    from repro.quant.calibrate import quantize_kv
    kk, kv = jax.random.split(jax.random.PRNGKey(3))
    k = jax.random.normal(kk, (L, Hkv, P, ps, D), jnp.float32)
    v = jax.random.normal(kv, (L, Hkv, P, ps, D), jnp.float32)
    scales = {}
    if quantized:
        k, ks, v, vs = quantize_kv(k, v)
        scales = {"k_scales": ks[:, :, :, None, :],
                  "v_scales": vs[:, :, :, None, :]}
    lanes = [(0, 0)] * 4 + [(0, pool_head_dim(D) - D)]
    return jnp.pad(k, lanes), jnp.pad(v, lanes), scales


def _layer_of(pool_k, pool_v, scales, layer, head_dim):
    return pool_k[layer, ..., :head_dim], pool_v[layer, ..., :head_dim], {
        name: sc[layer][:, :, 0] for name, sc in scales.items()}


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("pack_gqa", [True, False], ids=["packed", "rows"])
@pytest.mark.parametrize("kernel", ["paged_decode", "paged_verify"])
def test_stacked_pool_reads_layer_in_place(kernel, pack_gqa, quantized):
    """A stacked, lane-padded pool read at layer l gives the 4-D call on
    pool[l], with and without int8 scales, for both head packings. The
    zero lanes add only zeros, which may reorder a float32 sum: equal to
    float32 rounding."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_decode import paged_decode
    from repro.kernels.paged_verify import paged_verify
    kp, vp, scales = _stacked_pool(quantized)
    B, Hq, D, K = 2, 4, 16, 3
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0]], jnp.int32)
    lens = jnp.asarray([29, 17], jnp.int32)
    if kernel == "paged_decode":
        fn = paged_decode
        q = jax.random.normal(jax.random.PRNGKey(5), (B, Hq, D), jnp.float32)
    else:
        fn = paged_verify
        q = jax.random.normal(jax.random.PRNGKey(5), (B, K, Hq, D),
                              jnp.float32)
    for layer in (0, 2):
        got = fn(q, kp, vp, tables, lens, layer=jnp.int32(layer),
                 pack_gqa=pack_gqa, **scales)
        lk, lv, lscales = _layer_of(kp, vp, scales, layer, D)
        want = fn(q, lk, lv, tables, lens, pack_gqa=pack_gqa, **lscales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kernel", ["paged_decode", "paged_verify"])
def test_stacked_pool_tunes_as_one_layer(kernel, tmp_path):
    """``ops`` builds the tuning context of a stacked pool from one
    layer's dims, so phi3-mini's served pool (all 32 layers, 40 pages of
    256) picks the config its single-layer pool picks."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import AnalyticalMeasure, Autotuner, TuningCache
    from repro.core.tuner import SHIPPED_DB
    from repro.kernels import ops

    cfg = get_config("phi3-mini-3.8b")
    tuner = Autotuner(
        cache=TuningCache(cache_dir=str(tmp_path), overlay_path=SHIPPED_DB),
        backend=AnalyticalMeasure(CHIP), on_miss="tune")
    B, W, ps = 12, 17, 256
    pool = (cfg.n_layers, cfg.n_kv_heads, 40, ps, cfg.head_dim)
    q = (B, cfg.n_heads, cfg.head_dim)
    if kernel == "paged_verify":
        q = (B, 4) + q[1:]
    sds = jax.ShapeDtypeStruct
    fn = getattr(ops, kernel)
    picks = []
    for shape, layer in ((pool, 31), (pool[1:], 0)):
        jax.eval_shape(
            lambda q_, k_, v_, t_, l_: fn(q_, k_, v_, t_, l_, layer=layer,
                                          tuner=tuner),
            sds(q, jnp.bfloat16), sds(shape, jnp.bfloat16),
            sds(shape, jnp.bfloat16), sds((B, W), jnp.int32),
            sds((B,), jnp.int32))
        ctx, config = tuner.last_dispatch(kernel)
        picks.append((ctx.signature(), config))
    assert picks[0] == picks[1]
