"""The harness end to end on the CPU at smoke size: a cell added as files,
``correct`` true for the program as it is and false with its timed path
broken, the control failing the limit, and no run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import (BENCH, DATA, ROOT, SMOKE_CELL,  # noqa: F401
                           bench_root, bench_run, make_root)
from harness import engine, flops, peaks, spec

SECONDS = 3.0
NEW_METRIC = {"name": "steps_in_window", "unit": "steps", "better": "higher",
              "source": "program_counter", "layer": "scheduler",
              "moves": "output_tokens_per_s", "workloads": [SMOKE_CELL]}


def run_smoke(bench_run, root, seed=5, traced=False):
    cell = spec.load_cell(SMOKE_CELL, root)
    return bench_run.run_cell(cell, seed, SECONDS, traced,
                              require_tpu=False, root=root)


def assert_harness_unchanged(root):
    """Every harness and metric file of ``root`` is bench/'s own."""
    for sub in ("harness", "metrics"):
        for name in os.listdir(os.path.join(BENCH, sub)):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, sub, name)) as a, \
                        open(os.path.join(root, "bench", sub, name)) as b:
                    assert a.read() == b.read(), name


def test_a_cell_is_files_and_entries(tmp_path, bench_run):
    """A configuration, a mix and a per-layer metric are new files plus
    entries in BENCHMARK.json; no file of the harness changes."""
    root = make_root(tmp_path, extra_metrics=[NEW_METRIC])
    with open(os.path.join(root, "bench", "metrics",
                           "steps_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.steps_in_window())\n")
    assert_harness_unchanged(root)
    cell = spec.load_cell(SMOKE_CELL, root)
    assert cell.config["name"] == "phi3-mini-smoke"
    assert cell.traffic["rate_per_s"] == 4.0
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    out = run_smoke(bench_run, root, traced=True)
    assert out["metrics"]["steps_in_window"]["value"] > 0
    assert out["metrics"]["queue_wait_ms.p90"]["unit"] == "ms"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"


def add_moe_cell(root):
    """The smoke sparse-expert decoder with sliding-window layers and its
    counts module, added to ``root`` as files and entries."""
    configs = os.path.join(root, "bench", "configs")
    shutil.copy(os.path.join(DATA, "smoke-moe-config.json"),
                os.path.join(configs, "smoke-moe.json"))
    shutil.copy(os.path.join(DATA, "smoke_moe_counts.py"), configs)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append({"name": "smoke-moe", "source": "tests",
                         "file": "bench/configs/smoke-moe.json",
                         "reduced": [], "why": "CPU-sized"})
    b["workloads"].append({"name": "smoke-moe.chat", "config": "smoke-moe",
                           "traffic": "smoke", "chips": 1, "why": "tests"})
    with open(path, "w") as f:
        json.dump(b, f)
    return spec.load_cell("smoke-moe.chat", root)


def test_a_non_dense_architecture_is_files_and_entries(tmp_path):
    """Experts, a window, leading dense layers, a head_dim other than
    hidden / heads, bias vectors and counts of its own enter as files and
    entries: the harness builds what the file states and stops only where
    the program's paged path does."""
    from repro.models import lm
    from repro.models.config import MoEConfig
    root = make_root(tmp_path)
    cell = add_moe_cell(root)
    assert_harness_unchanged(root)
    conf = cell.config
    cfg = engine.model_config(conf)
    assert (cfg.family, cfg.window, cfg.first_dense, cfg.norm) == \
        ("moe", 32, 1, "layernorm")
    assert cfg.moe == MoEConfig(n_experts=4, top_k=2, d_ff_expert=32)
    assert (cfg.head_dim, cfg.d_model // cfg.n_heads) == (32, 16)
    assert cfg.layer_kinds() == ["attn_mlp"] + ["attn_moe"] * 3

    params = engine.draw_params(cfg, 11)
    dense, moe = params["u0"]["l0"], params["u0"]["l1"]
    assert dense["ffn"]["wi"].shape == (64, 2 * 128)
    assert moe["ffn"]["wi"].shape == (4, 64, 2 * 32)
    assert dense["mix"]["wq"].shape == (64, 4 * 32)
    for leaf in (dense["mix"]["bq"], dense["mix"]["bo"], dense["ln1"]["b"],
                 moe["mix"]["bv"], moe["ln2"]["b"]):
        assert not np.asarray(leaf).any()
    assert (np.asarray(moe["ln1"]["w"]) == 1).all()
    assert np.asarray(moe["ffn"]["router"]).std() > 0

    counts = flops.counts_for(conf, cell.bench_dir)
    assert counts is not flops
    assert counts.__file__ == os.path.join(root, "bench", "configs",
                                           "smoke_moe_counts.py")
    roofline = spec.load_module(os.path.join(
        BENCH, "metrics", "paged_decode_roofline.py"), "bench_roofline_moe")
    pk, ctx = peaks.PEAKS["TPU v5 lite"], [10, 100, 60]
    groups = counts.paged_decode_groups(conf, ctx)
    assert [n for n, _ in groups] == [1, 3]
    full, windowed = (flops.paged_decode_cost(conf, c)
                      for c in (ctx, [10, 32, 32]))
    assert [c for _, c in groups] == [full, windowed]
    least = [n * max(c["flops"] / pk["bf16_flops_per_s"],
                     c["bytes"] / pk["hbm_bytes_per_s"]) for n, c in groups]
    assert roofline.least_seconds(counts, conf, ctx, pk) == \
        pytest.approx(sum(least))
    assert least[0] > 0 and least[1] > 0
    assert counts.decode_flops(conf, ctx) < flops.decode_flops(conf, ctx)
    assert counts.prefill_flops(conf, 40, 8) < flops.prefill_flops(
        conf, 40, 8)
    assert roofline.kernel_pattern(conf).endswith(
        ",32\\] custom-call:tpu_custom_call$")

    with pytest.raises(NotImplementedError,
                       match="paged serving supports") as err:
        engine.build(conf, 11)
    assert err.traceback[-1].name == "_check_paged"
    assert err.traceback[-1].frame.f_globals["__name__"] == lm.__name__


@pytest.mark.parametrize("program,field", [
    ({"experts": 4}, "experts"),
    ({"moe": {"n_experts": 4, "top_k": 2, "d_ff_expert": 32,
              "routed": 1}}, "routed"),
])
def test_an_unknown_program_field_is_named(program, field):
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        conf = json.load(f)
    conf["program"] = program
    with pytest.raises(ValueError, match=repr(field)):
        engine.model_config(conf)


def test_sound_run_is_correct(bench_run, bench_root):  # noqa: F811
    out = run_smoke(bench_run, bench_root, seed=2**33 + 1)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert out["checks"]["served_tokens"]["value"] >= 100


def _break(monkeypatch, fault):
    """Break the engine's timed path where it is built."""
    from repro.serving import scheduler

    build = scheduler.ServingEngine._build_jits

    def broken(self):
        build(self)
        decode = self._decode_fn

        def step(params, token, cache, *rest):
            toks, ok, new = decode(params, token, cache, *rest)
            if fault == "token":          # a token altered where produced
                toks = toks.at[0].set((toks[0] + 1) % self.cfg.vocab_size)
            else:                         # the step leaves its state as is
                new = cache
            return toks, ok, new

        self._decode_fn = step

    monkeypatch.setattr(scheduler.ServingEngine, "_build_jits", broken)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, bench_run,
                                          bench_root):  # noqa: F811
    _break(monkeypatch, fault)
    out = run_smoke(bench_run, bench_root)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["max"]


def test_control_fails_the_limit(bench_root):  # noqa: F811
    """The reference one precision down (fp8) in the program's place,
    judged by the run's own comparison and limits, is not correct; the
    program over the same sample is."""
    sys.path.insert(0, BENCH)
    control = spec.load_module(os.path.join(BENCH, "control.py"),
                               "bench_control_module")
    cell = spec.load_cell(SMOKE_CELL, bench_root)
    run = control.bench_run
    built, residents, arrivals = run.setup(cell, 7, SECONDS)
    win = run.measure(built, residents, arrivals, SECONDS)
    run.free(built)
    r = control.readings(cell, win, 7)
    assert r["program"]["correct"], r
    assert not r["control"]["correct"], r
    prog, ctrl = r["program"]["checks"], r["control"]["checks"]
    assert prog["served_tokens"] == ctrl["served_tokens"]
    assert prog["served_tokens"]["value"] >= 100
    gap = ctrl["served_logit_gap"]
    assert gap["value"] > gap["max"]


@pytest.mark.parametrize("points,want", [
    # The chat sweep on one v5e: tokens/s stop rising at 0.30 req/s.
    ([(0.2, 44.4), (0.25, 54.0), (0.3, 65.9), (0.35, 65.2), (0.45, 64.5)],
     0.3),
    # The two rag sweeps on one v5e: on the plateau from 0.8 req/s, where
    # one high reading at 1.2 does not move the knee.
    ([(0.35, 21.0), (0.45, 24.7), (0.55, 30.1), (0.65, 36.1), (0.8, 43.5),
      (0.9, 41.6), (1.0, 43.5), (1.2, 45.3), (1.5, 37.8)], 0.8),
    # Still rising at the highest rate swept: no knee yet.
    ([(0.35, 21.0), (0.45, 24.7), (0.55, 30.1), (0.65, 36.1), (0.8, 40.5)],
     None),
    # On the plateau from the lowest rate swept: sweep lower.
    ([(0.8, 43.5), (0.9, 41.6), (1.0, 43.5), (1.2, 45.3), (1.5, 37.8)],
     None),
])
def test_knee_is_where_throughput_stops_rising(points, want):
    sweep = spec.load_module(os.path.join(BENCH, "sweep.py"),
                             "bench_sweep_module")
    pts = [{"rate_per_s": r, "output_tokens_per_s": t} for r, t in points]
    pts.append({"rate_per_s": 9.0, "error": "lost"})
    assert sweep.knee(pts) == want


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "phi3-mini.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_file_names_what_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
