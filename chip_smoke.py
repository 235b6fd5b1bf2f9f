"""Smoke run of the paged serving engine on the chip.

    python chip_smoke.py              # one TPU
    python chip_smoke.py --chips 4    # TP=4 over four TPUs against TP=1

Serves phi3-mini-3.8b at its published widths and full depth (32 layers,
d_model 3072, bf16, random weights from seed 0) through the normal entry
point, ``repro.launch.serve --decode-impl paged``: 8 requests of 256-512
prompt tokens, 32 generated tokens each, 8 concurrent sequences, prefill
chunks of 128. Then it checks the result on the chip.

One chip:
  * the compiled ``paged_decode`` kernel on the served page pool agrees
    with ``ref.paged_decode``;
  * each request's first generated token agrees with the dense XLA
    forward (``lm.forward``) on the same prompt;
  * the compiled decode step holds a ``tpu_custom_call`` (the kernel is
    compiled by Mosaic, not interpreted);
  * the tuner served no quarantined or fallback config, and tuned
    analytically for the attached chip (never by wall-clock timing).

``--chips 4``: the TP=1 engine on one chip and the TP=4 engine over four
chips serve the same prompts; first tokens must agree, and TP=4's prefill
logits may stray from a float32 reference at most twice as far as TP=1's.

Timings printed here describe a smoke run, not metrics. The last line of
standard output is ``{"ok": true, "device": {...}}``; any failure raises
and the script exits non-zero. There is no CPU fallback: without a TPU it
exits non-zero and claims nothing.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVE_ARGS = ["--arch", "phi3-mini-3.8b", "--full-config",
              "--decode-impl", "paged", "--requests", "8",
              "--prompt-len", "512", "--gen", "32", "--max-batch", "8",
              "--prefill-chunk", "128"]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileTimes:
    """Backend compile seconds per jitted function name, from JAX's own
    monitoring events (no extra compiles)."""

    def __init__(self, jax):
        self.seconds = collections.defaultdict(float)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.seconds[str(kw.get("fun_name", "?"))] += duration

    def report(self, label: str) -> None:
        top = sorted(self.seconds.items(), key=lambda kv: -kv[1])
        print(f"[smoke run] {label} compile s: "
              + ", ".join(f"{k}={v:.1f}" for k, v in top[:4])
              + f"; all programs={sum(self.seconds.values()):.1f}")


def serve(args, label: str):
    """One served run through ``repro.launch.serve``; checks every request
    reached a terminal state with no failure."""
    from repro.launch import serve as serve_lib
    t0 = time.perf_counter()
    engine, reqs, res = serve_lib.main(args)
    wall = time.perf_counter() - t0
    print(f"[smoke run] {label}: {res['steps']} steps, "
          f"{res['generated_tokens']} generated tokens, "
          f"{wall:.1f} s wall including compiles")
    check(res["terminal_requests"] == len(reqs),
          f"{label}: non-terminal requests: {res}")
    check(res["failed_requests"] == 0 and res["timed_out_requests"] == 0,
          f"{label}: failed requests: {res}")
    check(all(len(r.tokens) == r.max_new_tokens for r in reqs),
          f"{label}: a request stopped short")
    return engine, reqs, res


def padded_prompts(reqs):
    import numpy as np
    lens = np.array([len(r.prompt) for r in reqs], np.int32)
    toks = np.zeros((len(reqs), int(lens.max())), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :lens[i]] = r.prompt
    return toks, lens


def dense_last_logits(engine, reqs):
    """Logits after each prompt from the dense XLA forward (no pages)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    toks, lens = padded_prompts(reqs)

    @jax.jit
    def f(params, tokens, last):
        logits, _ = lm.forward(params, engine.cfg, tokens)
        return jnp.take_along_axis(logits, last[:, None, None], 1)[:, 0]

    return f(engine.params, toks, lens - 1).astype(jnp.float32)


def paged_last_logits(engine, reqs):
    """Logits after each prompt from one paged-prefill step of ``engine``'s
    model (its TP layout included) over a fresh pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distribution import tp as tp_lib
    from repro.models import lm
    cfg, ps = engine.cfg, engine.pool.page_size
    toks, lens = padded_prompts(reqs)
    B, L = toks.shape
    per_row = -(-L // ps)
    specs = lm.paged_cache_specs(cfg, 1 + B * per_row, ps)
    tables = 1 + np.arange(B * per_row, dtype=np.int32).reshape(B, per_row)
    if engine.mesh is None:
        cache = lm.init_paged_cache(cfg, 1 + B * per_row, ps)

        def step(params, tokens, cache, tables, start):
            return lm.prefill_paged(params, cfg, tokens, cache, tables,
                                    start, engine.opts)
    else:
        cache = tp_lib.init_cache(specs, engine.mesh)
        step = tp_lib.make_tp_prefill_paged(cfg, engine.mesh,
                                            opts=engine.opts)

    @jax.jit
    def f(params, tokens, cache, tables, last):
        logits, _ = step(params, tokens, cache, tables,
                         jnp.zeros((B,), jnp.int32))
        return jnp.take_along_axis(logits, last[:, None, None], 1)[:, 0]

    return f(engine.params, toks, cache, tables, lens - 1).astype(
        jnp.float32)


def f32_last_logits(cfg, reqs, mesh):
    """The float32 reference: logits after each prompt from the dense XLA
    forward in float32 at the highest matmul precision, on the served
    weights (seed 0, as ``serve.py`` draws them, in bf16) widened to
    float32. 15 GB of float32 weights fit no single chip, so XLA partitions
    the program over ``mesh`` from the weights' layout alone; the
    shard_map code of ``distribution/tp.py`` plays no part in it."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.distribution import tp as tp_lib
    from repro.models import lm
    from repro.models.param import init_params
    shardings = tp_lib.param_shardings(cfg, mesh)
    params = jax.jit(
        lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p),
        out_shardings=shardings)(
            init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg), shardings))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    toks, lens = padded_prompts(reqs)

    @jax.jit
    def f(params, tokens, last):
        logits, _ = lm.forward(params, cfg32, tokens)
        return jnp.take_along_axis(logits, last[:, None, None], 1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return f(params, toks, lens - 1)


def check_first_tokens(label, got, want, ref_logits, noise=0.0):
    """First tokens ``got`` against ``want``. A token that differs passes
    only as a near tie: its logit in ``ref_logits`` (the reference run's
    logits after each prompt) within 0.05 standard deviations, or within
    five times ``noise`` (the measured RMS logit error between the two
    runs, where known), of the wanted token's. With ~32k roughly Gaussian
    logits the top two sit ~0.2 sigma apart on average, and bf16 rounding
    through 32 layers can swap a pair that close; a broken path would
    also miss the exact matches."""
    import numpy as np
    ref = np.asarray(ref_logits)
    exact = 0
    for i, (tok, w) in enumerate(zip(got, want)):
        if tok == w:
            exact += 1
            continue
        gap = float(ref[i, w] - ref[i, tok])
        sigma = float(ref[i].std())
        print(f"  {label}: request {i} token {tok} vs reference {w}: "
              f"gap {gap:.4f} = {gap / sigma:.3f} sigma")
        check(abs(gap) <= max(0.05 * sigma, 5 * noise),
              f"{label}: request {i} first token {tok} is not the "
              f"reference's {w} nor a near tie")
    print(f"[check] {label}: {exact}/{len(got)} first tokens equal, "
          f"the rest near ties")
    check(2 * exact >= len(got), f"{label}: under half the tokens equal")


def smoke_one_chip(jax, dev):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.hardware import chip_for_device_kind
    from repro.core.measure import AnalyticalMeasure
    from repro.core.tuner import default_tuner
    from repro.kernels import ref
    from repro.kernels.mode import resolve_interpret
    from repro.kernels.paged_decode import paged_decode

    tuner = default_tuner()
    check(isinstance(tuner.backend, AnalyticalMeasure),
          f"tuner backend {tuner.backend.name} would tune by timing")
    check(tuner.backend.chip == chip_for_device_kind(dev.device_kind),
          f"tuner chip {tuner.backend.chip.name} is not the attached one")
    check(resolve_interpret() is False, "kernels would run interpreted")
    times = CompileTimes(jax)
    engine, reqs, _ = serve(SERVE_ARGS, "serve phi3-mini-3.8b tp=1")
    times.report("serve")
    st = tuner.stats()
    print(f"[check] tuner: {st['quarantines']} quarantines, "
          f"{st['fallback_serves']} fallback serves, backend "
          f"{tuner.backend.name}")
    check(st["quarantines"] == 0 and st["fallback_serves"] == 0,
          f"tuner degraded: {st}")

    # The decode step as served: compiled with the kernel, not interpreted.
    slots = engine.scheduler.max_batch
    t0 = time.perf_counter()
    hlo = engine._decode_fn.lower(
        engine.params, np.zeros((slots, 1), np.int32), engine.cache,
        engine.scheduler.block_tables(), np.zeros((slots,), np.int32),
        np.ones((slots, 1), np.float32),
    ).compile().as_text()
    n_custom = hlo.count("tpu_custom_call")
    print(f"[check] decode step: {n_custom} tpu_custom_call in compiled "
          f"HLO (re-lowered in {time.perf_counter() - t0:.1f} s)")
    check(n_custom > 0, "decode step has no compiled Pallas kernel")

    # paged_decode on the served pool of the last layer against the
    # reference. Tables cover the pages the run wrote (page 0 is the
    # scratch page); lengths are the requests' final resident lengths.
    B = len(reqs)
    _, kcfg = tuner.last_dispatch("paged_decode")
    pool = engine.cache["u0"]["l0"]["self"]
    kp, vp = pool["k_pages"][-1], pool["v_pages"][-1]
    n_pages = kp.shape[1]
    max_pages = engine.scheduler.max_pages
    tbl = 1 + np.arange(B * max_pages).reshape(B, max_pages) % (n_pages - 1)
    lens = np.array([len(r.prompt) + len(r.tokens) - 1 for r in reqs],
                    np.int32)
    cfg = engine.cfg
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (B, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    out = jax.jit(lambda *a: paged_decode(
        *a, block_kv=kcfg["block_kv"], pack_gqa=kcfg["pack_gqa"]))(
            q, kp, vp, tbl, lens)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode(q.astype(jnp.float32), kp, vp, tbl, lens)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    # The kernel rounds its output to bf16 (2**-8 relative) and sums in
    # another order than the float32 reference: allow two bf16 units in
    # the last place at the largest output magnitude.
    tol = 2.0 ** -7 * float(jnp.max(jnp.abs(want)))
    print(f"[check] paged_decode (config {kcfg}) vs ref.paged_decode on "
          f"the served pool: max |err| {err:.3e}, tolerance {tol:.3e}")
    check(err <= tol, "paged_decode disagrees with the reference")

    dense = np.asarray(dense_last_logits(engine, reqs))
    check_first_tokens("paged engine vs dense forward",
                       [r.tokens[0] for r in reqs], dense.argmax(-1), dense)
    return 1


def smoke_four_chips(jax):
    import numpy as np
    check(len(jax.devices()) >= 4, f"--chips 4 needs four devices, "
          f"found {len(jax.devices())}")
    times = CompileTimes(jax)
    engine, reqs1, _ = serve(SERVE_ARGS + ["--tp", "1"],
                             "serve phi3-mini-3.8b tp=1")
    first1 = [r.tokens[0] for r in reqs1]
    logits1 = np.asarray(paged_last_logits(engine, reqs1))
    del engine
    gc.collect()
    engine, reqs4, _ = serve(SERVE_ARGS + ["--tp", "4"],
                             "serve phi3-mini-3.8b tp=4")
    check(all(np.array_equal(a.prompt, b.prompt)
              for a, b in zip(reqs1, reqs4)), "prompts differ between runs")
    logits4 = np.asarray(paged_last_logits(engine, reqs4))
    cfg, mesh = engine.cfg, engine.mesh
    del engine
    gc.collect()
    ref = np.asarray(f32_last_logits(cfg, reqs1, mesh))
    times.report("tp=1, tp=4 and the float32 reference")
    # Both runs compute the same bf16 math in another order: TP=4 splits
    # each row-parallel matmul's contraction over the chips and adds the
    # bf16 partials in 64 all-reduces, a few more roundings beside the
    # hundreds TP=1 already makes. So TP=4 may stray from the float32
    # reference somewhat more than TP=1, never twice as far; a wrong shard
    # layout strays by the logits' own scale (RMS ~ their std).
    rms1, rms4 = (float(np.sqrt(np.mean((lg - ref) ** 2)))
                  for lg in (logits1, logits4))
    print(f"[check] prefill logits vs float32 reference (std "
          f"{ref.std():.3f}): RMS err tp=1 {rms1:.3e}, tp=4 {rms4:.3e}; "
          f"max |tp=4 - tp=1| {np.max(np.abs(logits4 - logits1)):.3e}")
    check(rms4 <= 2 * rms1, "tp=4 logits stray from the float32 reference "
          "more than twice as far as tp=1's")
    check_first_tokens("tp=1 vs float32 reference", first1, ref.argmax(-1),
                       ref, noise=rms1)
    check_first_tokens("tp=4 vs tp=1", [r.tokens[0] for r in reqs4],
                       first1, logits1, noise=rms1 + rms4)
    return 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare TP=4 over four chips against TP=1 "
                         "(and run nothing else)")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_KERNEL_GUARD"):
        raise SystemExit("chip_smoke: refusing to run with "
                         "REPRO_KERNEL_GUARD set: the guard would serve the "
                         "reference oracle in place of a failing kernel")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: run from the repository checkout "
                         f"({e})")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device_kind {dev.device_kind!r}, {len(jax.devices())} "
          f"device(s); compilation cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    count = (smoke_four_chips(jax) if args.chips == 4
             else smoke_one_chip(jax, dev))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:count])
    print(f"[smoke run] total {time.perf_counter() - t0:.1f} s, "
          f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB, max over "
          f"{count} device(s))")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
