"""The control of ``correct``: the reference itself, one precision down,
in the program's place, judged by the run's own comparison and limits.

    python3 bench/control.py --workload phi3-mini.chat --seeds 5,6,7

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, the window at the cell's own load, the sample), then two passes
of the plain reference over the sampled prompts and served tokens:

  * fp8 (``precision="fp8"``): at each position, the token the lower
    precision puts first: the control's served token there;
  * float32, as every run makes it, reading at each position the logit of
    the program's served token and of the control's.

Both go through ``check.widest_gap`` and ``run.judge`` with the cell's
limits, exactly as a run's tokens do. Prints one JSON line per seed,
``{"seed", "program": {"correct", "checks"}, "control": {...}}``; the
control has to come out not correct. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import check, spec  # noqa: E402


def readings(cell, win, seed: int) -> dict:
    """The program's and the control's results over one sample."""
    picked = check.sample(win.records + win.residents, seed)
    seqs, targets, spans = check.sequences(picked)
    ref = spec.reference_module(cell.bench_dir, cell.config)
    low = ref.forward(cell.config, seed, seqs, targets, precision="fp8")
    both = [np.concatenate([t, o["top"][:, None]], 1)
            for t, o in zip(targets, low)]
    hi = ref.forward(cell.config, seed, seqs, both, precision="float32")
    out = {"seed": seed}
    for who, column in (("program", 0), ("control", 1)):
        ok, checks = bench_run.judge(check.widest_gap(hi, spans, column),
                                     bench_run.limits(cell))
        out[who] = {"correct": bool(ok), "checks": checks}
        for name, c in checks.items():
            bench_run.log(f"{who} seed {seed} check {name} " + " ".join(
                f"{k} {v}" for k, v in c.items()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's "
                         "run_seconds)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    bench_run.find_devices(cell.chips)
    bench_run.enable_compile_cache(bench_run.ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        built, residents, arrivals = bench_run.setup(cell, seed, seconds)
        win = bench_run.measure(built, residents, arrivals, seconds)
        bench_run.free(built)
        print(json.dumps(readings(cell, win, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
