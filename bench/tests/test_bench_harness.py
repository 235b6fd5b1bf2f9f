"""The harness end to end on the CPU at smoke size: a cell added as files,
``correct`` true for the program as it is and false with its timed path
broken, the control failing the limit, and no run without a TPU."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench_testlib import (BENCH, ROOT, SMOKE_CELL, bench_root,  # noqa: F401
                           bench_run, make_root)
from harness import spec

SECONDS = 3.0
NEW_METRIC = {"name": "steps_in_window", "unit": "steps", "better": "higher",
              "source": "program_counter", "layer": "scheduler",
              "moves": "output_tokens_per_s", "workloads": [SMOKE_CELL]}


def run_smoke(bench_run, root, seed=5, traced=False):
    cell = spec.load_cell(SMOKE_CELL, root)
    return bench_run.run_cell(cell, seed, SECONDS, traced,
                              require_tpu=False, root=root)


def test_a_cell_is_files_and_entries(tmp_path, bench_run):
    """A configuration, a mix and a per-layer metric are new files plus
    entries in BENCHMARK.json; no file of the harness changes."""
    root = make_root(tmp_path, extra_metrics=[NEW_METRIC])
    with open(os.path.join(root, "bench", "metrics",
                           "steps_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.steps_in_window())\n")
    for sub in ("harness", "metrics"):
        for name in os.listdir(os.path.join(BENCH, sub)):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, sub, name)) as a, \
                        open(os.path.join(root, "bench", sub, name)) as b:
                    assert a.read() == b.read(), name
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
