"""Find a cell and everything it names, by name, under the benchmark root.

  BENCHMARK.json                   the cells, configurations and metrics
  bench/configs/<config>.json      a configuration (the entry's ``file``)
  bench/configs/<reference>.py     its plain reference, named in the file
                                   as ``"reference"``
  bench/configs/<counts>.py        its work counts, named in the file as
                                   ``"counts"``; without it, the dense
                                   decoder's (``harness/flops.py``)
  bench/traffic/<traffic>.json     a traffic mix (``harness/traffic.py``)
  bench/metrics/<metric>.py        a per-layer metric: ``read(run)``

A configuration file gives the published widths (``head_dim`` where it is
not hidden_size / num_attention_heads) and, in an optional ``program``
block, any field of the program's ``ModelConfig`` that the dense default
does not set: ``{"family": "moe", "moe": {"n_experts": 64, ...},
"window": 1024}`` (``harness/engine.py``). A counts module defines

  prefill_flops(config, start, tokens) -> float   a chunk of ``tokens``
                                   prompt tokens after ``start`` resident
  decode_flops(config, contexts) -> float         one token per sequence
  paged_decode_groups(config, contexts) -> [(n_layers, {"flops", "bytes"})]
                                   one layer's paged attention for each
                                   group of layers that reads alike

Adding a configuration, an architecture, a mix or a metric is new files
and an entry in ``BENCHMARK.json``; nothing here changes.

A cell reports each metric whose entry lists it under ``workloads``, and
each metric whose entry has no such list. In ``BENCHMARK.json`` only
``output_tokens_per_s`` and ``setup_s`` have none; ``ttft_p90_ms``,
``itl_p95_ms`` and every per-layer metric list their cells. So a new cell
adds its name to each list whose metric it can report: a cell in no list
reports ``output_tokens_per_s`` and ``setup_s`` alone, and no per-layer
metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict                  # the configuration file's contents
    traffic: Dict                 # the mix file's contents
    end_to_end: List[Dict]        # the cell's end-to-end metrics
    per_layer: List[Dict]         # the cell's per-layer metrics
    bench_dir: str


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``/BENCHMARK.json, with its files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                bench_dir=bench_dir)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    return load_module(path, "bench_metric_" + metric.replace(".", "_")
                       .replace("-", "_")).read


def reference_module(bench_dir: str, config: Dict):
    """The plain reference that the configuration file names."""
    ref = config.get("reference")
    if ref is None:
        raise ValueError(f"{config['name']}: the configuration names no "
                         f"reference, so no run of it can be judged")
    return load_module(os.path.join(bench_dir, "configs", ref + ".py"),
                       "bench_reference_" + ref)
