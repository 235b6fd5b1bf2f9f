"""The harness end to end on the CPU at smoke size: a cell added as files,
``correct`` true for the program as it is and false with its timed path
broken, the control failing the limit, and no run without a TPU."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from typing import Optional, Tuple

import jax
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


# The tests' non-dense configurations: (file, cell, [(layers, contexts)]
# of the paged attention groups its counts give at contexts [10, 100, 60]).
ARCHS = {
    "moe-window": ("smoke-moe-config.json", "smoke-moe.chat",
                   [(1, [10, 100, 60]), (3, [10, 32, 32])]),
    "moe-only": ("smoke-moe-only-config.json", "smoke-moe-only.chat",
                 [(3, [10, 100, 60])]),
}
E2E_LISTED = {"ttft_p90_ms", "itl_p95_ms"}


def add_moe_cell(root, arch="moe-window", listed=True):
    """A smoke sparse-expert decoder (``ARCHS``) and its counts module,
    added to ``root`` as files and entries. ``listed``: the cell's name is
    added to every metric's ``workloads`` list, as a new cell does."""
    data, cell, _ = ARCHS[arch]
    configs = os.path.join(root, "bench", "configs")
    shutil.copy(os.path.join(DATA, data),
                os.path.join(configs, arch + ".json"))
    shutil.copy(os.path.join(DATA, "smoke_moe_counts.py"), configs)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append({"name": arch, "source": "tests",
                         "file": f"bench/configs/{arch}.json",
                         "reduced": [], "why": "CPU-sized"})
    b["workloads"].append({"name": cell, "config": arch,
                           "traffic": "smoke", "chips": 1, "why": "tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        if listed and "workloads" in m:
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(b, f)
    return spec.load_cell(cell, root)


def hand_built(conf):
    """The ``ModelConfig`` that a configuration file states, built here
    from its widths and its ``program`` block."""
    from repro.models.config import ModelConfig, MoEConfig
    program = dict(conf["program"])
    moe = MoEConfig(**program.pop("moe"))
    dense = ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", conf["hidden_size"]
                          // conf["num_attention_heads"]),
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=conf["rope_theta"], dtype=conf["deployment"]["dtype"])
    return dataclasses.replace(dense, moe=moe, **program)


def deepest_repo_frame(err):
    """(module, file) of the deepest frame of ``err`` that lies in the
    repo's own code, the program's or the benchmark's; frames of JAX and
    other libraries are passed over."""
    repro_dir = os.path.join(ROOT, "src", "repro")
    found, tb = None, err.__traceback__
    while tb is not None:
        f = tb.tb_frame
        path = os.path.abspath(f.f_code.co_filename)
        if path.startswith((repro_dir + os.sep, BENCH + os.sep)):
            found = (f.f_globals.get("__name__", ""), path)
        tb = tb.tb_next
    return found


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_a_non_dense_architecture_is_files_and_entries(arch, tmp_path):
    """Experts, with or without a window, leading dense layers, a head_dim
    other than hidden / heads and counts of its own enter as files and
    entries: the harness builds the model config that the file states,
    draws the program's tree as the program specifies it, and stops only
    where the program does, whatever the program serves."""
    from repro.models import lm
    from repro.models.param import ParamSpec
    _, _, want_groups = ARCHS[arch]
    root = make_root(tmp_path)
    cell = add_moe_cell(root, arch)
    assert_harness_unchanged(root)
    conf = cell.config
    cfg = engine.model_config(conf)
    assert cfg == hand_built(conf)

    params = engine.draw_params(cfg, 11)
    specs = lm.lm_specs(cfg)
    is_spec = lambda x: isinstance(x, ParamSpec)  # noqa: E731
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(specs, is_leaf=is_spec)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for (path, leaf), s in zip(leaves, jax.tree_util.tree_leaves(
            specs, is_leaf=is_spec)):
        where = jax.tree_util.keystr(path)
        assert (leaf.shape, leaf.dtype) == (tuple(s.shape),
                                            jnp.dtype(s.dtype)), where
        a = np.asarray(leaf)
        if s.init == "ones":
            assert (a == 1).all(), where
        elif s.init == "zeros":
            assert not a.any(), where
        else:
            assert a.std() > 0, where

    counts = flops.counts_for(conf, cell.bench_dir)
    assert counts is not flops
    assert counts.__file__ == os.path.join(root, "bench", "configs",
                                           "smoke_moe_counts.py")
    roofline = spec.load_module(os.path.join(
        BENCH, "metrics", "paged_decode_roofline.py"), "bench_roofline_moe")
    pk, ctx = peaks.PEAKS["TPU v5 lite"], [10, 100, 60]
    groups = counts.paged_decode_groups(conf, ctx)
    assert groups == [(n, flops.paged_decode_cost(conf, c))
                      for n, c in want_groups]
    least = [n * max(c["flops"] / pk["bf16_flops_per_s"],
                     c["bytes"] / pk["hbm_bytes_per_s"]) for n, c in groups]
    assert roofline.least_seconds(counts, conf, ctx, pk) == \
        pytest.approx(sum(least))
    assert all(t > 0 for t in least)
    windowed = len(want_groups) > 1
    assert (counts.decode_flops(conf, ctx) < flops.decode_flops(
        conf, ctx)) == windowed
    assert (counts.prefill_flops(conf, 40, 8) < flops.prefill_flops(
        conf, 40, 8)) == windowed
    assert roofline.kernel_pattern(conf).endswith(
        f",{cfg.head_dim}\\] custom-call:tpu_custom_call$")

    try:
        built = engine.build(conf, 11)
    except Exception as err:          # the program refuses what it lacks
        module, path = deepest_repo_frame(err)
        assert module.split(".")[0] == "repro", (module, path, err)
        assert not path.startswith(BENCH + os.sep), (path, err)
    else:
        assert built.cfg == engine.model_config(conf)
        assert built.cfg.scan_plan() == hand_built(conf).scan_plan()


@pytest.mark.parametrize("listed", [True, False])
def test_a_new_cell_reports_what_it_is_listed_for(listed, tmp_path):
    """``ttft_p90_ms``, ``itl_p95_ms`` and every per-layer metric list
    their cells. A cell added to each list reports every end-to-end and
    per-layer metric; a cell in no list reports only the metrics that list
    no cells: today ``output_tokens_per_s`` and ``setup_s``, and no
    per-layer metric."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    e2e = {m["name"] for m in b["end_to_end"]}
    unlisted = {m["name"] for m in b["end_to_end"] if "workloads" not in m}
    assert e2e >= E2E_LISTED | {"output_tokens_per_s", "setup_s"}
    assert unlisted >= {"output_tokens_per_s", "setup_s"}
    assert not unlisted & E2E_LISTED
    cell = add_moe_cell(make_root(tmp_path), listed=listed)
    names = {m["name"] for m in cell.end_to_end}
    per_layer = [m["name"] for m in cell.per_layer]
    if listed:
        assert names == e2e
        assert per_layer == [m["name"] for m in b["per_layer"]]
    else:
        assert names == unlisted
        assert per_layer == [m["name"] for m in b["per_layer"]
                             if "workloads" not in m]


def test_a_configuration_without_a_reference_cannot_be_judged(tmp_path):
    cell = add_moe_cell(make_root(tmp_path))
    assert "reference" not in cell.config
    with pytest.raises(ValueError, match=cell.config["name"]):
        spec.reference_module(cell.bench_dir, cell.config)


@pytest.mark.parametrize("program,field", [
    ({"no_such_field": 4}, "no_such_field"),
    ({"moe": {"n_experts": 4, "top_k": 2, "d_ff_expert": 32,
              "no_such_field": 1}}, "no_such_field"),
])
def test_an_unknown_program_field_is_named(program, field):
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        conf = json.load(f)
    conf["program"] = program
    with pytest.raises(ValueError, match=repr(field)):
        engine.model_config(conf)


@dataclasses.dataclass(frozen=True)
class _Inner:
    sizes: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class _Outer:
    pattern: Tuple[Optional[int], ...] = ()
    inner: Optional[_Inner] = None


def test_a_list_in_the_program_block_is_a_tuple():
    """A per-layer field stated as a JSON list (nested lists too) is built
    as a tuple, so the frozen config stays hashable."""
    got = engine._fields(_Outer, {"pattern": [None, 32, [1, 2]],
                                  "inner": {"sizes": [4, [5]]}}, "test")
    assert got == {"pattern": (None, 32, (1, 2)),
                   "inner": _Inner(sizes=(4, (5,)))}
    hash(_Outer(**got))


@pytest.mark.parametrize("rope", [
    {"rope_type": "yarn", "factor": 4.0},
    {"full_attention": {"rope_type": "yarn", "factor": 4.0},
     "sliding_attention": {"rope_type": "default"}},
])
def test_a_rope_the_program_lacks_is_refused(rope):
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        conf = json.load(f)
    conf["rope_parameters"] = rope
    with pytest.raises(ValueError, match="rope_type='yarn'; the program "
                                         "implements 'default'"):
        engine.model_config(conf)


def test_the_default_rope_is_accepted():
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        conf = json.load(f)
    plain = engine.model_config(conf)
    conf["rope_parameters"] = {
        "rope_type": "default", "rope_theta": conf["rope_theta"],
        "full_attention": {"rope_type": "default"}}
    assert engine.model_config(conf) == plain


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
