"""Serving launcher: batched prefill + greedy decode for any registry arch.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v2-lite-16b \
      --requests 4 --prompt-len 48 --gen 16

``--decode-impl`` selects the serving architecture:

  full    — static batch, dense per-request KV caches, einsum decode
  pallas  — static batch, dense caches, registry decode kernels
            (gqa_decode_ragged / mla_decode) on the hot path
  paged   — paged KV pool + continuous batching (repro/serving/): requests
            are admitted as pages free up, chunked prefill interleaves with
            decode, and the autotuned ``paged_decode`` kernel runs over
            block tables. The pool's page size comes from the tuner's
            deployment-level ``paged_decode`` config (docs/serving.md).

``--quant`` selects a quantization policy (repro/quant/): ``w8a8`` /
``w8a16`` quantize the MLP projection weights (per-channel int8, QTensor
params), ``kv8`` serves an int8 KV cache — dense caches under
``--decode-impl pallas`` (the ``gqa_decode_kv8`` kernel) and int8 pages
under ``--decode-impl paged`` (the ``paged_decode`` kernel dequantizing
in-kernel). Each policy's kernels tune as their own scenarios (dtype is
part of the cache key), warm-started from the shipped DB.

``--prefix-cache`` (paged only) turns on cross-request prefix caching
(repro/serving/prefix_cache.py): retired sequences park their KV pages
in a radix tree keyed by token ids, later requests with a shared prefix
(system prompts) reuse the cached full pages via refcount bumps and
prefill only their marginal suffix, and LRU eviction reclaims cold
refcount-1 pages under pool pressure. Composes with ``--quant kv8`` and
``--tp N``; output stays token-for-token equal to the uncached path.

``--tp N`` serves tensor-parallel over an N-device mesh (both dense and
paged paths, distribution/tp.py): params are column/row-sharded, KV
caches and page pools kv-head-sharded, and the decode kernels launch on
per-shard local shapes — their tuned configs live under mesh-signature
cache keys (shipped for TP=1/2/4 by gen_shipped_db). On a CPU-only host
run with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

With ``--on-miss heuristic`` the decode hot path never tunes inline:
kernels launch with their heuristic defaults while the daemon background
worker drains the tuning queue off the critical path (paper Q4.4), so
later steps of the same process pick up tuned configs from the cache.

``--config-source`` picks where dispatches resolve configs: ``db``
(default) serves point-tuned shipped-DB entries with the config
portfolio (core/portfolio.py, "a few fit most") covering cache misses;
``portfolio`` serves the K-member portfolio first — the small-DB
deployment mode — falling back to point entries; ``tune`` ignores the
portfolio. Combined with ``--drift-report``, flagged regressions feed
the online retuning loop: the engine enqueues the drifted scenario, the
background worker retunes it, the fresh winner is admitted into the
live portfolio, and the engine re-jits so subsequent dispatches use it.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.models.param import init_params
from repro.obs import drift as drift_lib
from repro.obs import trace as trace_lib
from repro.obs.metrics import default_registry


def serve_paged(args, cfg, tuner):
    """Continuous batching over a paged KV pool. Returns the engine, the
    requests it served and ``ServingEngine.run``'s result."""
    from repro.core.config_space import TuningContext
    from repro.quant import get_policy
    from repro.serving import Request, ServingEngine

    B, P, G = args.requests, args.prompt_len, args.gen
    max_seq_len = P + G
    # Deployment-level tuning sizes the pool: look up the CANONICAL
    # deployment scenario (page_size free, full-config head geometry,
    # shipped dtype) — exactly what gen_shipped_db ships, so a warm
    # process reads the overlay instead of tuning at startup. A cold
    # cache tunes it once here (pipelined engine / analytical default).
    # The kv8 policy serves int8 pages: its deployment scenario is the
    # SAME shapes at dtype "int8" — a distinct cache key, because the
    # winning layout shifts with the halved KV traffic (also shipped).
    # Under --tp N the lookup is the SHARDED deployment: per-shard local
    # shapes plus the mesh signature — the shipped TP entries, never the
    # unsharded global-shape ones.
    from repro.configs.gen_shipped_db import (
        SHIP_DTYPE, paged_deployment_shapes, tp_mesh_signature,
    )
    policy = get_policy(None if args.quant == "none" else args.quant)
    kv8 = policy is not None and policy.quantizes_kv
    chip = getattr(tuner.backend, "chip", None) or \
        getattr(getattr(tuner.backend, "analytical", None), "chip", None)
    full_cfg = get_config(args.arch)
    if args.tp > 1:
        # Fail fast BEFORE the deployment lookup: a non-dividing tp would
        # floor the head counts into a nonexistent scenario and (under
        # on_miss=tune) waste minutes tuning garbage inline. Both views
        # must divide: the full config keys the lookup, the (possibly
        # smoke-scaled) serving config builds the engine.
        from repro.distribution.tp import check_tp_supported
        check_tp_supported(full_cfg, args.tp)
        check_tp_supported(cfg, args.tp)
    ctx = TuningContext(
        chip=chip, shapes=paged_deployment_shapes(full_cfg, tp=args.tp),
        dtype="int8" if kv8 else SHIP_DTYPE,
        mesh=tp_mesh_signature(args.tp))
    deploy_cfg = tuner.best_config("paged_decode", ctx)
    # Speculative decoding (--speculative): the paged_verify deployment
    # entry is tuned with draft_k free, so its winner doubles as the
    # recommended draft width when the flag gives no explicit K.
    spec_k = 0
    if args.speculative is not None:
        verify_cfg = tuner.best_config("paged_verify", ctx)
        spec_k = (args.speculative if args.speculative >= 2
                  else int(verify_cfg["draft_k"]))
        print(f"speculative decoding: deployment config {verify_cfg} "
              f"-> draft_k {spec_k}")
    # Clamp to the largest tunable page size that a single sequence can
    # still fill (tiny smoke traces would otherwise waste a whole page).
    from repro.kernels.ops import PAGED_DECODE
    ps_values = next(p.values for p in PAGED_DECODE.space.params
                     if p.name == "page_size")
    page_size = max(v for v in ps_values
                    if v <= max(min(ps_values), max_seq_len))
    page_size = min(page_size, deploy_cfg["page_size"])
    print(f"paged serving: deployment config {deploy_cfg} "
          f"-> page_size {page_size}")

    # Observability (docs/observability.md): the tracer/metrics/drift
    # handles only exist when a flag asks for them, so the default serve
    # path stays bit-identical and instrumentation-free.
    tracer = None
    if args.trace_out:
        tracer = trace_lib.Tracer()
        trace_lib.set_active(tracer)       # tuner events join the trace
    metrics = default_registry() if args.metrics_out else None
    drift = None
    if args.drift_report:
        drift = drift_lib.DriftDetector()
        drift_lib.set_active(drift)        # eager ops.py dispatches too

    shardings = None
    if args.tp > 1:
        # Weights are drawn already sharded, never whole on one device.
        from repro.distribution import tp as tp_lib
        shardings = tp_lib.param_shardings(cfg, tp_lib.make_tp_mesh(args.tp))
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg), shardings)
    rng = np.random.default_rng(0)
    pages_per_seq = -(-(max_seq_len + args.prefill_chunk) // page_size)
    engine = ServingEngine(
        cfg, params, num_pages=1 + args.max_batch * pages_per_seq,
        page_size=page_size, max_batch=args.max_batch,
        max_seq_len=max_seq_len + args.prefill_chunk,
        prefill_chunk=args.prefill_chunk,
        quant=None if args.quant == "none" else args.quant, tp=args.tp,
        prefix_cache=args.prefix_cache, speculative=spec_k,
        tracer=tracer, metrics=metrics, drift=drift)
    plan = None
    if args.inject_faults:
        from repro.serving import FaultPlan, faults as fault_lib
        plan = FaultPlan.parse_spec(args.inject_faults)
        fault_lib.install(plan)
        print(f"fault injection: {args.inject_faults!r} "
              f"({len(plan.events)} events)")

    reqs = []
    # A shared system prompt heads every request when prefix caching is
    # on — the chat-traffic shape the radix tree exists for. Without the
    # cache, keep the fully-random prompts (the PR 3 smoke behavior).
    # The shared prompt must span at least one full page or no request
    # can ever hit (only full pages are shareable, and the match is
    # capped at prompt_len - 1): grow it to the page boundary and shrink
    # the per-request suffix budget so prompts stay within P.
    sys_len = max(1, P // 2)
    if args.prefix_cache:
        sys_len = min(max(sys_len, page_size), max(1, P - 1))
    sys_prompt = rng.integers(1, cfg.vocab_size, sys_len,
                              dtype=np.int64).astype(np.int32)
    for i in range(B):
        if args.prefix_cache:
            sfx = rng.integers(1, cfg.vocab_size,
                               int(rng.integers(1, max(2, P - sys_len))),
                               dtype=np.int64).astype(np.int32)
            prompt = np.concatenate([sys_prompt, sfx])
        else:
            plen = int(rng.integers(max(1, P // 2), P + 1))
            prompt = rng.integers(1, cfg.vocab_size, plen,
                                  dtype=np.int64).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=G))
    t0 = time.perf_counter()
    try:
        res = engine.run(reqs)
    finally:
        if plan is not None:
            from repro.serving import faults as fault_lib
            fault_lib.install(None)
    # One structured summary instead of ad-hoc wall-time prints: every
    # number a smoke job or a human wants is in this dict, including the
    # p50/p99 TTFT and inter-token latency computed from the per-request
    # token timestamps (Request.token_times).
    summary = {
        "requests": res["requests"],
        "generated_tokens": res["generated_tokens"],
        "steps": res["steps"],
        "wall_ms": round(res["wall_s"] * 1e3, 1),
        "tokens_per_s": round(res["tokens_per_s"], 1),
        "latency": {k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in res["latency"].items()},
        "lifecycle": {
            "preemptions": res["preemptions"],
            "resumes": res["resumes"],
            "failed": res["failed_requests"],
            "timed_out": res["timed_out_requests"],
            "terminal": res["terminal_requests"],
        },
    }
    if "speculative" in res:
        summary["speculative"] = res["speculative"]
    if "drift" in res:
        summary["drift"] = res["drift"]
    print("run report:", json.dumps(summary, sort_keys=True))
    # Every submitted request must land in a terminal state — the smoke
    # gate for the faults-smoke CI job: faults degrade requests, they
    # never wedge or crash the engine.
    assert res["terminal_requests"] == len(reqs), \
        f"non-terminal requests after drain: {res}"
    if plan is not None:
        from repro.core.tuner import default_tuner
        st = default_tuner().stats()
        print(f"kernel guard: {st.get('quarantines', 0)} quarantines, "
              f"{st.get('fallback_serves', 0)} fallback serves; "
              f"{len(plan.log)} fault events fired")
    if tuner.portfolio is not None:
        st = tuner.stats()
        ps = tuner.portfolio.stats()
        print(f"portfolio: {st.get('portfolio_serves', 0)} serves, "
              f"{st.get('portfolio_updates', 0)} admissions, "
              f"{st.get('drift_retunes', 0)} drift retunes "
              f"(selector: {ps['exact_hits']} exact / "
              f"{ps['nearest_hits']} nearest / "
              f"{ps['fallback_hits']} fallback)")
    engine.scheduler.check_invariants()
    if engine.prefix_cache is not None:
        stats = engine.prefix_cache.stats()
        print(f"prefix cache: {stats['hit_tokens']} prefill tokens avoided, "
              f"{stats['hits']}/{stats['lookups']} request hits, "
              f"{stats['parked_pages']} pages parked "
              f"({stats['evicted_pages']} evicted)")
        # Parked pages survive the drain by design (they ARE the cache);
        # everything else must be back in the free list.
        assert engine.pool.num_allocated == engine.prefix_cache.num_pages, \
            "page leak after drain (beyond parked cache pages)"
    else:
        assert engine.pool.num_allocated == 0, "page leak after drain"
    r0 = engine.scheduler.finished[0]
    print("sample:", r0.tokens[:12])
    print(f"total wall (incl jit): {(time.perf_counter()-t0)*1e3:.0f} ms")

    if tracer is not None:
        trace_lib.set_active(None)
        tracer.export(args.trace_out)
        print(f"trace: {len(tracer.events)} events "
              f"({tracer.dropped} dropped) -> {args.trace_out} "
              f"(open in chrome://tracing or ui.perfetto.dev)")
    if metrics is not None:
        metrics.export_json(args.metrics_out)
        print(f"metrics: snapshot -> {args.metrics_out}")
    if drift is not None:
        drift_lib.set_active(None)
        drift.export(args.drift_report)
        rep = drift.report()
        print(f"drift: {rep['tracked_keys']} keys tracked, "
              f"{rep['flagged_keys']} flagged -> {args.drift_report}")
    return engine, reqs, res


def serve_dense(args, cfg):
    """Static batch with dense per-request KV caches (the baseline).
    ``--tp N`` swaps the GSPMD step builders for the shard_map
    tensor-parallel ones (distribution/tp.py): column/row-sharded params,
    head-sharded caches, registry kernels launching on local shapes."""
    from repro.quant import quantize_params

    quant = None if args.quant == "none" else args.quant
    opts = lm.ForwardOpts(attn_chunk=64, decode_impl=args.decode_impl,
                          quant=quant)
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    params = quantize_params(params, quant, store="grid")
    B, P, G = args.requests, args.prompt_len, args.gen
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, P)),
                          jnp.int32)
    extra = {}
    if cfg.family == "encdec":
        extra["enc_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model)),
            jnp.dtype(cfg.dtype))
    if cfg.n_prefix:
        extra["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_prefix, cfg.d_model)),
            jnp.dtype(cfg.dtype))
    off = cfg.n_prefix or 0

    if args.tp > 1:
        from repro.distribution import tp as tp_lib
        from repro.quant import get_policy
        pol = get_policy(quant)
        if pol is not None and pol.quantizes_weights:
            raise SystemExit("--tp with w8a8/w8a16 is not supported yet "
                             "(QTensor param sharding); use kv8 or none")
        mesh = tp_lib.make_tp_mesh(args.tp)
        params = tp_lib.shard_params(params, cfg, mesh)
        print(f"tensor-parallel dense serving: tp={args.tp} "
              f"({len(jax.devices())} devices)")
        prefill = jax.jit(tp_lib.make_tp_prefill(cfg, mesh,
                                                 max_len=off + P + G,
                                                 opts=opts))
        decode = jax.jit(tp_lib.make_tp_decode(cfg, mesh, opts=opts))
    else:
        mesh = make_local_mesh()
        scfg = steps_lib.StepConfig(policy="serve_tp", opts=opts)
        prefill = jax.jit(steps_lib.make_prefill_step(cfg, scfg, mesh,
                                                      max_len=off + P + G))
        decode = jax.jit(steps_lib.make_decode_step(cfg, scfg, mesh))
    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts, **extra)
    jax.block_until_ready(logits)
    print(f"prefill {B}×{P}: {(time.perf_counter()-t0)*1e3:.0f} ms")
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    outs = [tok]
    for i in range(G - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(off + P + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    print(f"decode {B}×{G-1}: {dt*1e3:.0f} ms ({B*(G-1)/dt:.0f} tok/s)")
    print("sample:", np.concatenate(outs, 1)[0, :12].tolist())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="h2o-danube-3-4b")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--decode-impl", choices=("full", "pallas", "paged"),
                    default="full",
                    help="pallas = registry decode kernels on dense caches; "
                         "paged = continuous batching over the page pool "
                         "(paged_decode kernel)")
    ap.add_argument("--quant", choices=("none", "w8a8", "w8a16", "kv8"),
                    default="none",
                    help="quantization policy (repro.quant): w8a8/w8a16 "
                         "quantize the MLP projections, kv8 serves an int8 "
                         "KV cache (dense caches and paged pools)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (distribution/tp.py "
                         "shard_map serving). Needs >= N jax devices: on a "
                         "CPU host, launch with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--speculative", type=int, nargs="?", const=0,
                    default=None, metavar="K",
                    help="speculative decoding (paged only): draft-and-"
                         "verify with K draft positions per step through "
                         "the paged_verify kernel (serving/drafter.py "
                         "n-gram drafts, greedy accept/rollback — output "
                         "is token-identical to plain decode). Bare "
                         "--speculative takes K from the tuned "
                         "paged_verify deployment entry")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix caching (paged only): "
                         "retired sequences park their pages in a radix "
                         "tree and later requests reuse cached full-page "
                         "prefixes (docs/serving.md)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="concurrent sequences (paged only)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault injection (paged only): "
                         "comma-separated events — kexc@N[:kernel], "
                         "compile@N[:kernel], nan@N[:kernel], "
                         "logits@STEP[:slot], pool@STEP:PAGES[:HOLD], "
                         "slow@N:MS[:kernel] (latency inflation the drift "
                         "detector must flag), random@SEED[:N] "
                         "(serving/faults.py). The run asserts every "
                         "request still reaches a terminal state.")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked-prefill width (paged only)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(paged only): request lifecycle spans per slot, "
                         "scheduler phases per step, tuner events "
                         "(docs/observability.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot (paged only): "
                         "TTFT / inter-token histograms, step counters, "
                         "tuner + prefix-cache + scheduler stats")
    ap.add_argument("--drift-report", default=None, metavar="PATH",
                    help="track per-dispatch latency vs the tuning DB "
                         "(paged only) and write the drift report: EWMA "
                         "per cache key, flagged regressions")
    ap.add_argument("--on-miss", choices=("tune", "heuristic", "error"),
                    default=os.environ.get("REPRO_ON_MISS", "tune"),
                    help="tuner policy on cache miss; 'heuristic' keeps "
                         "tuning off the serving critical path and lets the "
                         "background worker converge the cache")
    ap.add_argument("--config-source",
                    choices=("portfolio", "db", "tune"),
                    default=os.environ.get("REPRO_CONFIG_SOURCE", "db"),
                    help="where dispatches resolve configs "
                         "(docs/autotuning.md): 'db' = point-tuned shipped "
                         "DB, with the config portfolio covering cache "
                         "misses; 'portfolio' = the K-member portfolio "
                         "first (a-few-fit-most serving), point entries as "
                         "fallback; 'tune' = ignore the portfolio entirely")
    args = ap.parse_args(argv)

    if args.inject_faults and args.decode_impl != "paged":
        raise SystemExit("--inject-faults requires --decode-impl paged "
                         "(the fault harness drives the paged scheduler)")
    if args.speculative is not None and args.decode_impl != "paged":
        raise SystemExit("--speculative requires --decode-impl paged "
                         "(draft-and-verify runs on the paged engine)")
    if ((args.trace_out or args.metrics_out or args.drift_report)
            and args.decode_impl != "paged"):
        raise SystemExit("--trace-out/--metrics-out/--drift-report require "
                         "--decode-impl paged (observability is wired "
                         "through the paged serving engine)")
    os.environ["REPRO_ON_MISS"] = args.on_miss
    os.environ["REPRO_CONFIG_SOURCE"] = args.config_source
    print(f"jax compilation cache: {enable_compile_cache()}")
    cfg = get_config(args.arch, smoke=not args.full_config)
    if args.decode_impl != "full":
        from repro.kernels.registry import list_kernels
        names = ", ".join(s.name for s in list_kernels(scenario="decode"))
        print(f"decode via registry kernels (available: {names})")
    # Any path can hit the process tuner (paged/pallas decode, rmsnorm,
    # ...); under the heuristic policy the queue must drain regardless of
    # which decode impl is serving.
    from repro.core.tuner import default_tuner
    tuner = default_tuner()
    # The tuner may predate this invocation (warm default_tuner), so apply
    # the requested source explicitly rather than relying on the env read
    # at construction time.
    if args.config_source in ("db", "portfolio"):
        if tuner.portfolio is None:
            from repro.core.portfolio import Portfolio
            tuner.attach_portfolio(Portfolio.load_shipped(),
                                   source=args.config_source)
        else:
            tuner.attach_portfolio(tuner.portfolio,
                                   source=args.config_source)
        if tuner.portfolio is not None:
            counts = tuner.portfolio.counts()
            print(f"config portfolio: {counts['members']} members over "
                  f"{counts['kernels']} kernels "
                  f"(source={args.config_source})")
        elif args.config_source == "portfolio":
            print("config portfolio: shipped artifact missing — "
                  "falling back to point-tuned DB lookups")
    else:
        tuner.attach_portfolio(None, source="tune")
    if tuner.on_miss == "heuristic":
        tuner.start_background_tuning()
        print("background tuning worker started (queue drains off the "
              "critical path)")
    out = None
    if args.decode_impl == "paged":
        out = serve_paged(args, cfg, tuner)
    else:
        serve_dense(args, cfg)
    if tuner.on_miss == "heuristic":
        # Idle now: give the worker a moment to finish the deferred tuning
        # this run enqueued, then report convergence. The queue empties when
        # the worker *pops* the last item, so also join the worker (stop
        # blocks until its in-flight tune finishes) before reporting.
        deadline = time.monotonic() + 30.0
        while len(tuner.queue) and time.monotonic() < deadline:
            time.sleep(0.1)
        tuner.stop_background_tuning(timeout=30.0)
        print(f"tuner stats: {tuner.stats()} (queue left: {len(tuner.queue)})")
    return out


if __name__ == "__main__":
    main()
