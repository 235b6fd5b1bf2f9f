"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload phi3-mini.chat --seed 17 --seconds 45 \
        --trace 0

Builds the cell's configuration (``BENCHMARK.json`` and the files it
names), warms the two step programs, drives the serving engine with the
cell's open-loop traffic for ``--seconds``, then checks what it served
against the plain reference. ``--trace 1`` records a device trace of part
of the window and reports the per-layer metrics instead of the
end-to-end ones. The last line of standard output is one JSON object.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import spec  # noqa: E402

TRACE_SECONDS = 8.0          # traced part of the window, at its end
SAMPLE_MIN_TOKENS = 100      # a comparison over fewer is no comparison


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def find_devices(chips: int, require_tpu: bool = True):
    """The devices a cell runs on; exits (code 3) where they are missing."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: no accelerator: {e}") from None
    if require_tpu and devs[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips; JAX found {len(devs)}")
        sys.exit(3)
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at one fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def setup(cell, seed: int, seconds: float):
    """Weights, engine and warm programs, the requests resident when the
    window opens, and the window's traffic."""
    from harness import engine as engine_lib
    from harness import traffic, window
    built = engine_lib.build(cell.config, seed)
    engine_lib.warm_up(built.engine)
    vocab = cell.config["vocab_size"]
    residents = window.warm_start(
        built.engine, traffic.warm_start(cell.traffic, seed, vocab))
    arrivals = traffic.generate(cell.traffic, seconds, seed, vocab)
    # What set-up left on the heap is kept out of the collector's full
    # passes, which would otherwise walk it inside the window.
    gc.collect()
    gc.freeze()
    return built, residents, arrivals


def measure(built, residents, arrivals, seconds, trace_dir=None):
    """The window. A traced run records the device over the window's last
    ``TRACE_SECONDS``, so that no token the window counts comes after the
    pause in which the profiler writes its trace; waits that span the
    pause are read without it (``result.Run.paused``)."""
    from harness import window
    tr = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(TRACE_SECONDS, seconds)
        tr = {"start": seconds - span, "seconds": span, "dir": trace_dir}
    return window.run(built.engine, arrivals, seconds, residents=residents,
                      trace=tr)


def free(built) -> None:
    """Drop the engine, its weights and its pool from the device."""
    import jax
    built.engine.params = built.engine.cache = None
    built.engine = None
    gc.collect()
    jax.clear_caches()


def compare(cell, win, seed: int, precision: str = "float32"):
    """The served tokens of a sample against the plain reference."""
    from harness import check
    picked = check.sample(win.records + win.residents, seed)
    seqs, targets, spans = check.sequences(picked)
    if not seqs:
        return {"served_logit_gap": float("inf"), "served_tokens": 0,
                "tokens_equal_to_reference": 0}
    ref = spec.reference_module(cell.bench_dir, cell.config)
    out = ref.forward(cell.config, seed, seqs, targets, precision=precision)
    return check.widest_gap(out, spans)


def limits(cell):
    return {"served_logit_gap": {"max": cell.config["check"][
        "served_logit_gap_max"]},
        "served_tokens": {"min": SAMPLE_MIN_TOKENS}}


def judge(found, lim) -> tuple:
    checks, ok = {}, True
    for name, bound in lim.items():
        v = found[name]
        (kind, limit), = bound.items()
        ok &= v <= limit if kind == "max" else v >= limit
        checks[name] = {"value": v, kind: limit}
    return ok, checks


def device_info(devs, trace_red=None):
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace_red:
        info["busy_s"] = trace_red["busy_s"]
        info["window_s"] = trace_red["window_s"]
    return info


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, root: str = ROOT) -> dict:
    """One run of ``cell``; returns the result line's object."""
    from harness import peaks as peaks_lib
    from harness import result, trace as trace_lib
    from harness.window import CompileCounter
    devs = find_devices(cell.chips, require_tpu)
    cache = enable_compile_cache(root) if require_tpu else None
    log(f"bench: {cell.name} seed {seed} on {len(devs)} x "
        f"{devs[0].device_kind}; compile cache {cache}")
    pk = peaks_lib.peaks(devs[0].device_kind) if require_tpu else \
        peaks_lib.PEAKS["TPU v5 lite"]
    compiled = CompileCounter(armed=True)
    built, residents, arrivals = setup(cell, seed, seconds)
    setup_s = time.perf_counter() - T_PROCESS
    compiled.armed = False
    log(f"bench: set-up {setup_s:.3f} s, of which {compiled.seconds:.3f} s "
        f"compiling {len(compiled.names)} programs")
    trace_dir = os.path.join(root, ".bench_trace") if traced else None
    win = measure(built, residents, arrivals, seconds, trace_dir)
    events = None
    if traced:
        events = trace_lib.capture(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    info = device_info(devs)
    sched = built.engine.scheduler
    log(f"bench: page size {built.page_size}, {built.num_pages} pages, "
        f"max_batch {sched.max_batch}; {len(win.steps)} steps, "
        f"{sched.preemptions} preemptions, {len(win.compiles)} compiles in "
        f"the window {sorted(set(win.compiles))}")
    lags = sorted(r.submitted - r.due for r in win.records)
    if lags:
        log(f"bench: generator lag ms p50 {lags[len(lags) // 2] * 1e3:.3f} "
            f"max {lags[-1] * 1e3:.3f} over {len(lags)} requests")
    run = result.Run(config=cell.config, window=win, peaks=pk,
                     memory_peak_bytes=info["memory_peak_bytes"],
                     events=events, setup_s=setup_s,
                     bench_dir=cell.bench_dir)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = spec.metric_reader(cell.bench_dir, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"attempted": len(win.records),
           "failed": sum(r.req.state.value in ("FAILED", "TIMED_OUT")
                         for r in win.records),
           "metrics": metrics}
    if traced:
        red = run.reduce()
        info = device_info(devs, red)
        out["breakdown"] = {"device_ops": red.get("device_ops", []),
                            "idle_gaps": red.get("idle_gaps", [])}
    out["device"] = info
    free(built)
    t = time.perf_counter()
    found = compare(cell, win, seed)
    log(f"bench: reference over {found['served_tokens']} served tokens in "
        f"{time.perf_counter() - t:.1f} s; "
        f"{found['tokens_equal_to_reference']} equal to its argmax")
    ok, checks = judge(found, limits(cell))
    out["correct"] = bool(ok)
    out["checks"] = checks
    for name, c in checks.items():
        log("check " + name + " " + " ".join(f"{k} {v}" for k, v in
                                             c.items()))
    return {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device", "breakdown", "checks") if k in out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
