"""The dense configurations read as they did before counts, head_dim and
the program's fields came from the configuration file: every value in
``data/golden-dense.json`` was computed by the dense-only harness (model
config built in code, head_dim = hidden_size / num_attention_heads, leaf
kinds told by name) and must come out equal to the last bit."""

import dataclasses
import gzip
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench_testlib import BENCH, DATA
from harness import engine, flops, peaks, result, spec, weights, window

CONFIGS = ("phi3-mini-3.8b", "phi4-mini-3.8b")
PEAKS = peaks.PEAKS["TPU v5 lite"]
SEED = 2**33 + 5
ROOFLINE = spec.load_module(
    os.path.join(BENCH, "metrics", "paged_decode_roofline.py"),
    "bench_metric_paged_decode_roofline_golden")
REFERENCE = spec.load_module(
    os.path.join(BENCH, "configs", "dense_rope_swiglu.py"),
    "bench_reference_golden")

with open(os.path.join(DATA, "golden-dense.json")) as f:
    GOLDEN = json.load(f)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def digest(x) -> str:
    a = np.asarray(jax.device_get(x))
    return (hashlib.sha256(a.tobytes()).hexdigest() + ":" + str(a.dtype)
            + str(a.shape))


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config(name):
    cfg = engine.model_config(config(name))
    assert dataclasses.asdict(cfg) == GOLDEN[name]["model_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_counts(name):
    c, g = config(name), GOLDEN[name]
    counts = flops.counts_for(c)
    assert counts is flops
    for start, tokens, want in g["prefill_flops"]:
        assert counts.prefill_flops(c, start, tokens) == want
    for ctx, want in g["decode_flops"]:
        assert counts.decode_flops(c, ctx) == want
    for ctx, want in g["roofline_least_s"]:
        assert ROOFLINE.least_seconds(counts, c, ctx, PEAKS) == want


@pytest.mark.parametrize("name", CONFIGS)
def test_kernel_pattern(name):
    assert ROOFLINE.kernel_pattern(config(name)) == \
        GOLDEN[name]["kernel_pattern"]


def recorded_run(conf, events):
    """A traced run over the recorded trace's steps, each decoding 12
    sequences at made-up contexts."""
    n = sum(h[0] == "bench.step" for h in events["host"])
    steps = [window.Step(t0=float(i), t1=i + 0.5, prefill_tokens=0,
                         prefill_start=0,
                         decode_ctx=[300 + 97 * i + 211 * b
                                     for b in range(12)],
                         cached_tokens=0, admitted_prompt_tokens=0,
                         waiting=0) for i in range(n)]
    win = window.Window(t0=0.0, t1=float(n), records=[], residents=[],
                        steps=steps, compiles=[],
                        trace={"start": 0.0, "stop_called": float(n)})
    return result.Run(config=conf, window=win, peaks=PEAKS,
                      memory_peak_bytes=0, events=events)


def test_recorded_trace_roofline():
    with gzip.open(os.path.join(DATA, "trace-phi3-chat-v5e.json.gz"),
                   "rt") as f:
        events = json.load(f)
    run = recorded_run(config("phi3-mini-3.8b"), events)
    assert ROOFLINE.read(run) == GOLDEN["recorded_roofline"]


def smoke_configs():
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        smoke = json.load(f)
    tied = dict(smoke, name="phi4-mini-smoke", tie_word_embeddings=True,
                num_key_value_heads=1,
                deployment=dict(smoke["deployment"], dtype="bfloat16"))
    return {"phi3-mini-smoke": smoke, "phi4-mini-smoke": tied}


@pytest.mark.parametrize("name", ["phi3-mini-smoke", "phi4-mini-smoke"])
def test_drawn_weights(name):
    """The program's tree as drawn for the engine, and the reference's
    own draws of the same leaves."""
    c = smoke_configs()[name]
    params = engine.draw_params(engine.model_config(c), SEED)
    got = {jax.tree_util.keystr(p): digest(v) for p, v in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    dm, key = REFERENCE.dims(c), weights.base_key(SEED)
    dtype = c["deployment"]["dtype"]
    for layer in range(dm.layers):
        for k, v in REFERENCE._layer_weights(key, dm, layer, dtype).items():
            got[f"reference/l{layer}/{k}"] = digest(v)
    tok, head = REFERENCE._tables(key, dm=dm, dtype=dtype)
    got["reference/tok"], got["reference/head"] = digest(tok), digest(head)
    got["reference/final_ln"] = digest(weights.draw(
        key, "final_ln/w", 0, (dm.d,), np.float32, init="ones"))
    assert got == GOLDEN["weights"][name]
