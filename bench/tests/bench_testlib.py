"""Helpers of the benchmark's tests: CPU only, smoke sizes, kernels in
interpret mode.

They put ``bench/`` (for ``harness``) and ``src/`` on the path, and build
throwaway benchmark roots under pytest's tmp_path: a copy of ``bench/``
with the files a test adds, beside a ``BENCHMARK.json`` that names them.
"""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CELL = "smoke.chat"


def make_root(path, extra_metrics=()):
    """A benchmark root at ``path``: bench/ copied, the smoke configuration
    and mix added as files, and one cell naming them."""
    shutil.copytree(BENCH, os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(DATA, "smoke-config.json"),
                os.path.join(path, "bench", "configs", "smoke.json"))
    shutil.copy(os.path.join(DATA, "smoke-traffic.json"),
                os.path.join(path, "bench", "traffic", "smoke.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "smoke", "source": "tests",
                         "file": "bench/configs/smoke.json", "reduced": [],
                         "why": "CPU-sized"})
    b["workloads"].append({"name": SMOKE_CELL, "config": "smoke",
                           "traffic": "smoke", "chips": 1, "why": "tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "phi3-mini.chat" in m.get("workloads", []):
            m["workloads"].append(SMOKE_CELL)
    b["per_layer"].extend(extra_metrics)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return str(path)


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("benchroot"))


@pytest.fixture(scope="module")
def bench_run():
    """``bench/run.py`` as a module (it is a script, not a package)."""
    from harness.spec import load_module
    return load_module(os.path.join(BENCH, "run.py"), "bench_run_module")
