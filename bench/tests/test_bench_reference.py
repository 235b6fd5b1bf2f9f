"""The plain reference against ``repro.models.lm`` on the SMOKE presets,
with the weights each side draws for itself from one seed."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import BENCH
from harness import engine, spec, weights

SEED = 2**32 + 77


def as_config(cfg):
    """A configuration file's keys for a ``ModelConfig``."""
    return {"name": cfg.name, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": 1e-06, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings,
            "reference": "dense_rope_swiglu",
            "deployment": {"dtype": cfg.dtype}}


def smoke(arch):
    from repro.configs import get_config
    return get_config(arch, smoke=True)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(BENCH, "configs",
                                         "dense_rope_swiglu.py"),
                            "bench_reference_test")


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "phi4-mini-3.8b"])
def test_reference_matches_the_model(arch, ref):
    from repro.models import lm
    cfg = smoke(arch)
    conf = as_config(cfg)
    assert engine.model_config(conf) == dataclasses.replace(
        cfg, name=conf["name"])
    params = engine.draw_params(cfg, SEED)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in (37, 5)]
    every = np.arange(cfg.vocab_size, dtype=np.int32)
    out = ref.forward(conf, SEED, seqs,
                      [np.tile(every, (len(s), 1)) for s in seqs])
    for s, o in zip(seqs, out):
        with jax.default_matmul_precision("highest"):
            want, _ = lm.forward(params, cfg, jnp.asarray(s[None]))
        want = np.asarray(want[0], np.float32)
        np.testing.assert_allclose(o["picked"], want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(o["best"], want.max(-1), rtol=2e-4,
                                   atol=2e-4)
        assert (o["top"] == want.argmax(-1)).mean() > 0.97


def test_program_tree_and_reference_draw_the_same_weights():
    cfg = dataclasses.replace(smoke("phi3-mini-3.8b"), dtype="bfloat16")
    params = engine.draw_params(cfg, SEED)
    key = weights.base_key(SEED)
    for layer in range(cfg.n_layers):
        w = weights.draw(key, "ffn/wi", layer, (cfg.d_model, 2 * cfg.d_ff),
                         jnp.bfloat16)
        assert np.array_equal(np.asarray(params["u0"]["l0"]["ffn"]["wi"]
                                         [layer]), np.asarray(w))
    assert np.array_equal(
        np.asarray(params["embed"]["tok"]),
        np.asarray(weights.draw(key, "embed/tok", 0,
                                (cfg.vocab_size, cfg.d_model), jnp.bfloat16)))
    assert (np.asarray(params["u0"]["l0"]["ln1"]["w"]) == 1).all()


def test_tied_embedding_is_drawn_at_the_head_scale(ref):
    """A tied embedding is also the output head, so it is drawn at
    1/sqrt(d_model) like the head; at scale 1 each token's own row would
    outscore every other and the model would repeat its input."""
    cfg = smoke("phi4-mini-3.8b")
    assert cfg.tie_embeddings
    params = engine.draw_params(cfg, SEED)
    tok = np.asarray(params["embed"]["tok"], np.float32)
    assert tok.std() == pytest.approx(cfg.d_model ** -0.5, rel=0.05)
    key = weights.base_key(SEED)
    assert np.array_equal(tok, np.asarray(weights.draw(
        key, "embed/tok", 0, tok.shape, jnp.float32, tied=True)))
    untied = weights.draw(key, "embed/tok", 0, tok.shape, jnp.float32)
    assert float(np.asarray(untied).std()) == pytest.approx(1.0, rel=0.05)
    s = np.random.default_rng(3).integers(1, cfg.vocab_size, 200) \
        .astype(np.int32)
    out = ref.forward(as_config(cfg), SEED, [s],
                      [np.zeros((len(s), 1), np.int32)])[0]
    assert (out["top"] == s).mean() < 0.2


def test_fp8_control_departs_from_float32(ref):
    cfg = smoke("phi4-mini-3.8b")
    conf = as_config(cfg)
    s = [np.random.default_rng(1).integers(1, cfg.vocab_size, 40)
         .astype(np.int32)]
    t = [np.zeros((40, 1), np.int32)]
    hi = ref.forward(conf, SEED, s, t)[0]
    lo = ref.forward(conf, SEED, s, t, precision="fp8")[0]
    assert np.abs(lo["best"] - hi["best"]).max() > 1e-3
    assert (lo["top"] == hi["top"]).mean() > 0.5


def test_seeds_beyond_32_bits():
    a = weights.base_key(2**31 + 12345)
    b = weights.base_key(2**40 + 3)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        weights.base_key(-1)
