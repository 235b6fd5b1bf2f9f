"""Find a cell's knee: the offered rate at which the tokens served stop
rising with the load.

    python3 bench/sweep.py --workload phi3-mini.chat --seconds 51 \
        --rates 0.2,0.3,0.4 [--warm-lifetime 30]

Runs the cell's window once per rate, each in a process of its own (this
parent never touches JAX), with the mix's ``rate_per_s`` replaced and,
where ``--warm-lifetime`` is given, ``warm_start`` set to rate x lifetime
(Little's law). Prints one JSON line per rate: tokens/s, TTFT p90, the
mean number of sequences decoded per step (over the rate: the decoding
lifetime, for Little's law), the mean waiting queue over the window's
first and last thirds, and how many requests due in the window were still
waiting when it closed; then one line with the knee (``knee``). No
correctness comparison is made; a sweep is not a benchmark run.

The knee is read from the throughput, not from the queue: where a request
lives about as long as the window, the warm start sets how many are
resident, and a queue can stay flat over one window above capacity.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def one(workload: str, rate: float, seconds: float, seed: int,
        warm: int) -> dict:
    sys.path.insert(0, BENCH)
    import run as bench_run
    from harness import spec, result, window
    window.DRAIN_LIMIT_S = 5.0       # a sweep reads the rate, not the tail
    cell = spec.load_cell(workload)
    cell.traffic["rate_per_s"] = rate
    if warm >= 0:
        cell.traffic["warm_start"] = warm
    bench_run.find_devices(cell.chips)
    bench_run.enable_compile_cache(bench_run.ROOT)
    built, residents, arrivals = bench_run.setup(cell, seed, seconds)
    win = bench_run.measure(built, residents, arrivals, seconds)
    run = result.Run(config=cell.config, window=win, peaks={},
                     memory_peak_bytes=0)
    third = seconds / 3
    q = [(s.t0 - win.t0, s.waiting) for s in win.steps
         if win.t0 <= s.t0 <= win.t1]
    first = [w for t, w in q if t < third]
    last = [w for t, w in q if t >= 2 * third]
    mean = lambda xs: sum(xs) / len(xs) if xs else None  # noqa: E731
    return {"rate_per_s": rate, "warm_start": len(residents),
            "requests": len(win.records),
            "output_tokens_per_s": run.tokens_in_window() / seconds,
            "ttft_p90_ms": result.percentile(run.ttft_ms(), 90),
            "itl_p95_ms": result.percentile(run.gaps_ms(), 95),
            "waiting_first_third": mean(first),
            "waiting_last_third": mean(last),
            "waiting_at_close": sum(r.running is None or r.running > win.t1
                                    for r in win.records),
            "preemptions": built.engine.scheduler.preemptions,
            "decode_slots_mean": mean([len(s.decode_ctx) for s in win.steps
                                       if s.decode_ctx])}


PLATEAU = 0.97               # within 3% of the plateau's tokens/s


def knee(points):
    """The lowest swept rate on the plateau: its tokens/s within
    ``PLATEAU`` of the median of its own and every higher rate's, so that
    one high or low reading above it moves nothing. None where the
    plateau starts at the lowest rate swept (sweep lower) or is not
    reached below the highest (sweep higher)."""
    pts = sorted((p["rate_per_s"], p["output_tokens_per_s"])
                 for p in points if "error" not in p)
    for i, (rate, tps) in enumerate(pts[:-1]):
        if tps >= PLATEAU * statistics.median(t for _, t in pts[i:]):
            return rate if i else None
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm-lifetime", type=float, default=None)
    ap.add_argument("--one", type=float, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--warm", type=int, default=-1, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one is not None:
        print(json.dumps(one(args.workload, args.one, args.seconds,
                             args.seed, args.warm)), flush=True)
        return 0
    points = []
    for rate in [float(r) for r in args.rates.split(",")]:
        warm = (round(rate * args.warm_lifetime)
                if args.warm_lifetime is not None else -1)
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--seconds", str(args.seconds), "--seed",
               str(args.seed), "--one", str(rate), "--warm", str(warm),
               "--rates", args.rates]
        out = subprocess.run(cmd, capture_output=True, text=True)
        lines = out.stdout.strip().splitlines()
        points.append(json.loads(lines[-1]) if out.returncode == 0 and lines
                      else {"rate_per_s": rate, "error": out.stderr[-2000:]})
        print(json.dumps(points[-1]), flush=True)
    print(json.dumps({"knee": knee(points)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
