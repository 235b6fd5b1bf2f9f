"""Observability layer (repro/obs/): tracer ring buffer + Chrome export
pinned byte-for-byte against a golden fixture (tests/fixtures/obs_trace/),
histogram bucket math, metrics registry snapshot/Prometheus shape, drift
detector flag/silence behavior, bounded token-time recording, and the
contract that turning instrumentation on changes ZERO generated tokens."""

import json
import math
import os

import numpy as np
import pytest

from repro.obs import (
    Counter, DriftDetector, Gauge, Histogram, MetricsRegistry, Tracer,
    VirtualClock,
)
from repro.obs import drift as drift_lib
from repro.obs import trace as trace_lib
from repro.serving import PagePool, Request, Scheduler
from repro.serving.scheduler import TOKEN_TIMES_CAP, latency_summary

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "obs_trace")


# ---------------------------------------------------------------------------
# Tracer: virtual clock, ring buffer, Chrome trace shape
# ---------------------------------------------------------------------------

def test_virtual_clock_ticks_deterministically():
    clk = VirtualClock(step=1e-6)
    assert [round(clk() * 1e6) for _ in range(4)] == [1, 2, 3, 4]
    clk2 = VirtualClock(step=0.5, start=10.0)
    assert clk2() == 10.5 and clk2() == 11.0


def test_tracer_span_nesting_and_instants():
    tr = Tracer(clock=VirtualClock())
    with tr.span("outer", track="sched", a=1):
        tr.instant("tick", track="sched", n=7)
        with tr.span("inner", track="sched"):
            pass
    phs = [(e["name"], e["ph"]) for e in tr.events]
    assert phs == [("outer", "B"), ("tick", "i"), ("inner", "B"),
                   ("inner", "E"), ("outer", "E")]
    ts = [e["ts"] for e in tr.events]
    assert ts == sorted(ts) and len(set(ts)) == len(ts)  # strictly increasing
    assert tr.events[1]["args"] == {"n": 7}
    assert tr.events[0]["args"] == {"a": 1}


def test_tracer_ring_buffer_drops_oldest():
    tr = Tracer(clock=VirtualClock(), capacity=4)
    for i in range(7):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert tr.dropped == 3
    assert [e["name"] for e in tr.events] == ["e3", "e4", "e5", "e6"]
    chrome = tr.to_chrome()
    assert chrome["metadata"] == {"dropped_events": 3, "capacity": 4}


def test_tracer_chrome_export_shape(tmp_path):
    tr = Tracer(clock=VirtualClock())
    tr.begin("req0", track="slot0", rid=0)
    tr.instant("cache_hit", track="tuner", kernel="paged_decode")
    tr.end("req0", track="slot0")
    path = str(tmp_path / "trace.json")
    tr.export(path)
    with open(path) as f:
        chrome = json.load(f)
    evs = chrome["traceEvents"]
    assert chrome["displayTimeUnit"] == "ms"
    # one thread_name metadata event per track, tids stable by creation
    meta = [e for e in evs if e["ph"] == "M"]
    assert [(m["tid"], m["args"]["name"]) for m in meta] == \
        [(0, "slot0"), (1, "tuner")]
    assert all(e["pid"] == 0 for e in evs)
    b, i, e = [ev for ev in evs if ev["ph"] in "BiE"]
    assert (b["ph"], i["ph"], e["ph"]) == ("B", "i", "E")
    assert b["tid"] == e["tid"] == 0 and i["tid"] == 1
    assert i["s"] == "t" and i["args"] == {"kernel": "paged_decode"}


def test_active_tracer_helpers_are_noops_when_uninstalled():
    assert trace_lib.get_active() is None
    trace_lib.active_instant("nope")            # must not raise
    with trace_lib.active_span("nope") as tr:
        assert tr is None
    tracer = Tracer(clock=VirtualClock())
    old = trace_lib.set_active(tracer)
    try:
        trace_lib.active_instant("yes", track="t")
        with trace_lib.active_span("s", track="t"):
            pass
        assert [e["name"] for e in tracer.events] == ["yes", "s", "s"]
    finally:
        trace_lib.set_active(old)


# ---------------------------------------------------------------------------
# Golden fixture: an 8-request scheduler trace under the virtual clock
# must export byte-for-byte what the committed fixture pins
# ---------------------------------------------------------------------------

def _golden_trace_text():
    """Drive a seeded 8-request trace through the scheduler (host-only,
    fake generation) with a virtual-clock tracer; return the exported
    Chrome JSON text."""
    tracer = Tracer(clock=VirtualClock())
    pool = PagePool(num_pages=24, page_size=4)
    sched = Scheduler(pool, max_batch=3, max_pages=pool.pages_for(48),
                      prefill_chunk=4, tracer=tracer)
    rng = np.random.default_rng(7)
    for i in range(8):
        sched.submit(Request(
            rid=i,
            prompt=rng.integers(1, 64,
                                int(rng.integers(2, 11))).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 5))))
    guard = 0
    while sched.has_work():
        guard += 1
        assert guard < 10_000, "trace did not drain"
        with tracer.span("step", track="scheduler", step=guard - 1):
            sched.retire_finished()
            sched.admit()
            chunk = sched.next_prefill()
            if chunk is not None:
                b, tokens, start, valid = chunk
                sched.mark_prefilled(b, valid)
                seq = sched.slots[b]
                if seq.prompt_done:
                    seq.req.tokens.append(seq.req.rid % 5 + 1)
            mask = sched.decode_mask()
            for b in np.nonzero(mask)[0]:
                sched.slots[int(b)].req.tokens.append(
                    sched.slots[int(b)].req.rid % 5 + 1)
            sched.advance_decoded(mask)
    sched.check_invariants()
    return json.dumps(tracer.to_chrome(), indent=1, sort_keys=True) + "\n"


def test_golden_chrome_trace():
    """The seeded scheduler trace must reproduce its committed Chrome
    export exactly — any drift in admission order, slot assignment, or
    event emission shows up as a byte diff here."""
    got = _golden_trace_text()
    with open(os.path.join(FIXTURES, "expected_trace.json")) as f:
        want = f.read()
    assert got == want, (
        "obs trace drifted from the golden fixture;\n"
        "if the change is intentional, regenerate with:\n"
        "  PYTHONPATH=src:tests python -c 'import test_obs as t;"
        " print(t._golden_trace_text(), end=\"\")'"
        f"\ngot:\n{got}")


def test_golden_trace_is_balanced_and_loadable():
    chrome = json.loads(_golden_trace_text())
    evs = chrome["traceEvents"]
    # every B has a matching E on the same track, and all 8 requests ran
    opens = {}
    for e in evs:
        if e["ph"] == "B":
            opens.setdefault((e["tid"], e["name"]), []).append(e)
        elif e["ph"] == "E":
            assert opens[(e["tid"], e["name"])], f"unmatched end: {e}"
            opens[(e["tid"], e["name"])].pop()
    assert all(not v for v in opens.values()), "unmatched span begins"
    req_spans = {e["name"] for e in evs
                 if e["ph"] == "B" and e["name"].startswith("req")}
    assert req_spans == {f"req{i}" for i in range(8)}
    # admit/retire are covered by the slot spans, not duplicated as
    # lifecycle instants; submit is the queued-side instant
    assert any(e["name"] == "submit" and e["ph"] == "i" for e in evs)
    assert not any(e["name"] in ("admit", "retire") and e["ph"] == "i"
                   for e in evs)


# ---------------------------------------------------------------------------
# Metrics: counters, gauges, histogram bucket math, registry exports
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    c = Counter("n")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_up_and_down():
    g = Gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_histogram_bucket_math():
    h = Histogram("lat", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 4.0, 99.0):   # bounds are inclusive
        h.observe(v)
    assert h.count == 5 and h.sum == 106.0
    assert h.bucket_counts == [2, 1, 1, 1]          # last slot = overflow
    assert h.cumulative() == [(1.0, 2), (2.0, 3), (5.0, 4), (math.inf, 5)]


def test_histogram_quantile_interpolation():
    h = Histogram("lat", buckets=(10.0, 20.0, 40.0))
    for _ in range(8):
        h.observe(5.0)                               # all in first bucket
    assert h.quantile(0.5) == pytest.approx(5.0)     # 0 + 0.5 * 10
    h2 = Histogram("lat2", buckets=(10.0, 20.0))
    h2.observe(5.0)
    h2.observe(15.0)
    # target q=1.0 -> 2 samples; second bucket [10, 20) holds the last
    assert h2.quantile(1.0) == pytest.approx(20.0)
    assert math.isnan(Histogram("e", buckets=(1.0,)).quantile(0.5))
    with pytest.raises(ValueError, match="outside"):
        h.quantile(1.5)


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", buckets=())


def test_registry_snapshot_and_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("serving_steps_total").inc(3)
    reg.gauge("queue_depth").set(2)
    reg.histogram("ttft_ms", buckets=(1.0, 10.0)).observe(4.0)
    reg.register_provider("tuner", lambda: {"hits": 5, "misses": 1})
    snap = reg.snapshot()
    assert snap["serving_steps_total"] == {"type": "counter", "value": 3.0}
    assert snap["queue_depth"] == {"type": "gauge", "value": 2.0}
    assert snap["ttft_ms"]["count"] == 1
    assert snap["ttft_ms"]["buckets"] == [[1.0, 0], [10.0, 1]]
    assert snap["providers"]["tuner"] == {"hits": 5, "misses": 1}
    # snake_case discipline: every key machine-parsable, no spaces/camel
    for key in snap:
        assert key == key.lower() and " " not in key, key
    assert reg.counter("serving_steps_total") is not None  # idempotent
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("serving_steps_total")


def test_registry_provider_error_captured():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("nope")

    reg.register_provider("bad", boom)
    snap = reg.snapshot()
    assert "RuntimeError" in snap["providers"]["bad"]["error"]
    assert "bad" not in reg.prometheus_text()        # skipped, not fatal


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(2)
    reg.histogram("lat_ms", buckets=(1.0, 5.0)).observe(0.5)
    reg.register_provider("cache", lambda: {"stats": {"hits": 3},
                                            "label": "x"})
    text = reg.prometheus_text()
    assert "# TYPE steps_total counter\nsteps_total 2" in text
    assert '# TYPE lat_ms histogram' in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_sum 0.5\nlat_ms_count 1" in text
    assert "cache_stats_hits 3" in text              # nested dict flattened
    assert "label" not in text                       # non-numeric dropped


def test_registry_export_json_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    path = str(tmp_path / "metrics.json")
    reg.export_json(path)
    with open(path) as f:
        assert json.load(f)["a_total"]["value"] == 1.0


# ---------------------------------------------------------------------------
# Drift detector: flags sustained slowdowns, stays silent on clean runs
# ---------------------------------------------------------------------------

def test_drift_flags_sustained_slowdown():
    det = DriftDetector(threshold=2.0, alpha=0.3, calibration=5)
    fired = []
    det.on_drift(lambda key, rep: fired.append((key, rep)))
    for _ in range(5):
        assert not det.observe("k1", 0.010, kernel="paged_decode")
    for _ in range(20):                              # sustained 5x regression
        det.observe("k1", 0.050)
    assert det.flagged() == ["k1"]
    assert len(fired) == 1                           # fires once per key
    key, rep = fired[0]
    assert key == "k1" and rep["kernel"] == "paged_decode"
    assert rep["ratio"] > 2.0
    report = det.report()
    assert report["flagged_keys"] == 1 and report["tracked_keys"] == 1
    assert report["entries"][0]["key"] == "k1"


def test_drift_silent_on_clean_run_with_compile_spike():
    det = DriftDetector(threshold=2.0, alpha=0.3, calibration=5)
    det.observe("k", 1.8)                 # first-call jit compile spike
    for _ in range(40):                   # steady state with jitter
        assert not det.observe("k", 0.004 + 0.001 * (_ % 3))
    assert det.flagged() == []


def test_drift_one_outlier_does_not_flag():
    det = DriftDetector(threshold=2.0, alpha=0.3, calibration=3)
    for _ in range(3):
        det.observe("k", 0.010)
    det.observe("k", 0.040)               # single GC pause / page fault
    for _ in range(10):
        det.observe("k", 0.010)
    assert det.flagged() == []


def test_drift_shipped_baseline_mode():
    det = DriftDetector(threshold=2.0, alpha=1.0, calibration=5,
                        use_shipped=True)
    assert not det.observe("k", 0.010, shipped=0.010)
    assert det.observe("k", 0.030, shipped=0.010)    # 3x the shipped metric
    rep = det.report()["entries"][0]
    assert rep["baseline_s"] == 0.010 and rep["shipped_metric"] == 0.010


def test_drift_validates_parameters():
    with pytest.raises(ValueError, match="threshold"):
        DriftDetector(threshold=1.0)
    with pytest.raises(ValueError, match="alpha"):
        DriftDetector(alpha=0.0)


def test_drift_export(tmp_path):
    det = DriftDetector()
    det.observe("k", 0.01, kernel="matmul")
    path = str(tmp_path / "drift.json")
    det.export(path)
    with open(path) as f:
        rep = json.load(f)
    assert rep["tracked_keys"] == 1 and rep["entries"][0]["samples"] == 1


def test_drift_active_handle():
    assert drift_lib.get_active() is None
    det = DriftDetector()
    old = drift_lib.set_active(det)
    try:
        assert drift_lib.get_active() is det
    finally:
        drift_lib.set_active(old)
    assert drift_lib.get_active() is None


# ---------------------------------------------------------------------------
# Bounded token-time recording + run-report latency summary
# ---------------------------------------------------------------------------

def test_token_times_capped_with_drop_counter():
    req = Request(rid=0, prompt=np.ones(4, np.int32), max_new_tokens=1)
    for i in range(TOKEN_TIMES_CAP + 10):
        req.note_token_time(float(i))
    assert len(req.token_times) == TOKEN_TIMES_CAP
    assert req.token_times_dropped == 10
    # ITL keeps working past the cap: the last timestamp always updates
    assert req.last_token_time == float(TOKEN_TIMES_CAP + 9)


def test_latency_summary_percentiles():
    reqs = []
    for i in range(2):
        r = Request(rid=i, prompt=np.ones(2, np.int32), max_new_tokens=3)
        r.submitted_at = 0.5 * i                     # each from its own submit
        for t in (1.0 + i, 1.5 + i, 2.0 + i):       # ttft 1s, 1.5s; itl 500ms
            r.note_token_time(t)
        reqs.append(r)
    s = latency_summary(reqs)
    assert s["ttft_samples"] == 2 and s["itl_samples"] == 4
    assert s["ttft_p50_ms"] == pytest.approx(1250.0)
    assert s["itl_p50_ms"] == pytest.approx(500.0)
    assert s["token_times_dropped"] == 0
    empty = latency_summary([])
    assert empty["ttft_p50_ms"] is None and empty["itl_samples"] == 0


# ---------------------------------------------------------------------------
# Scheduler + engine integration: instrumentation changes zero tokens
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(name="obs-t", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=128, dtype="float32")


def _seeded_reqs(rng, vocab, n=5):
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab, int(p)).astype(np.int32),
                    max_new_tokens=int(g))
            for i, (p, g) in enumerate(zip(rng.integers(2, 10, n),
                                           rng.integers(1, 5, n)))]


def test_observability_changes_zero_tokens():
    """Tokens with tracer+metrics+drift installed must be IDENTICAL to the
    uninstrumented run — observability is a read-only tap, never a
    numerics or scheduling input."""
    import jax

    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    kw = dict(num_pages=24, page_size=8, max_batch=3, max_seq_len=24,
              prefill_chunk=4)

    plain = ServingEngine(cfg, params, **kw)
    plain.run(_seeded_reqs(np.random.default_rng(3), cfg.vocab_size))
    want = {r.rid: list(r.tokens) for r in plain.scheduler.finished}

    tracer = Tracer(clock=VirtualClock())
    reg = MetricsRegistry()
    det = DriftDetector(calibration=2)
    obs = ServingEngine(cfg, params, tracer=tracer, metrics=reg,
                        drift=det, **kw)
    obs.run(_seeded_reqs(np.random.default_rng(3), cfg.vocab_size))
    got = {r.rid: list(r.tokens) for r in obs.scheduler.finished}
    assert got == want, "instrumentation changed generated tokens"

    # and the taps actually recorded the run
    names = {e["name"] for e in tracer.events}
    assert "serve.decode.launch" in names and "serve.prefill.launch" in names
    assert any(e["name"].startswith("req") for e in tracer.events)
    snap = reg.snapshot()
    assert snap["serving_steps_total"]["value"] > 0
    total = sum(len(v) for v in want.values())
    assert snap["serving_ttft_ms"]["count"] == len(want)
    assert (snap["serving_ttft_ms"]["count"]
            + snap["serving_inter_token_ms"]["count"]) == total
    assert det.entries, "drift detector saw no dispatches"
    assert "scheduler" in snap["providers"]


def test_metrics_step_counters_match_run_report():
    import jax

    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    reg = MetricsRegistry()
    engine = ServingEngine(cfg, params, num_pages=24, page_size=8,
                           max_batch=3, max_seq_len=24, prefill_chunk=4,
                           metrics=reg)
    res = engine.run(_seeded_reqs(np.random.default_rng(5),
                                  cfg.vocab_size))
    snap = reg.snapshot()
    assert snap["serving_steps_total"]["value"] == res["steps"]
    assert snap["serving_retired_total"]["value"] == res["requests"]
    assert snap["serving_decode_tokens_total"]["value"] <= \
        res["generated_tokens"]
    assert res["latency"]["ttft_samples"] == res["requests"]
    assert res["latency"]["ttft_p50_ms"] is not None


# ---------------------------------------------------------------------------
# Online retuning: drift flag -> background retune -> portfolio update ->
# re-jit with the fresh config (the serve-time half of ROADMAP item 5)
# ---------------------------------------------------------------------------

def test_drift_triggers_online_retune_end_to_end(tmp_path):
    """Serve with a forced slowdown on paged_decode and walk the whole
    online-retuning loop: the detector flags the dispatch key, the engine
    re-enqueues it through the default tuner, the flushed background tune
    admits the fresh winner into the live portfolio, and the NEXT run
    re-jits onto it and resets the detector — with the drift counters
    visible in both the run report and the metrics registry."""
    import jax

    from repro.core import get_chip
    from repro.core import tuner as tuner_mod
    from repro.core.cache import TuningCache
    from repro.core.measure import AnalyticalMeasure
    from repro.core.portfolio import PORTFOLIO_SCHEMA, Portfolio
    from repro.core.tuner import Autotuner
    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine
    from repro.serving import faults as fault_lib

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    kw = dict(num_pages=24, page_size=8, max_batch=3, max_seq_len=24,
              prefill_chunk=4)

    pf = Portfolio({"schema": PORTFOLIO_SCHEMA, "threshold": 0.1,
                    "max_members": 8, "source_entries": 0, "kernels": {}})
    tuner = Autotuner(cache=TuningCache(cache_dir=str(tmp_path / "dt")),
                      backend=AnalyticalMeasure(get_chip("tpu_v5e")),
                      on_miss="heuristic", portfolio=pf,
                      config_source="db")
    tuner_mod.set_default_tuner(tuner)
    try:
        det = DriftDetector(threshold=3.0, alpha=0.3, calibration=4)
        reg = MetricsRegistry()
        eng = ServingEngine(cfg, params, drift=det, metrics=reg, **kw)

        # Run 1 (clean): calibrates the decode key's baseline. No flags.
        res1 = eng.run(_seeded_reqs(np.random.default_rng(11),
                                    cfg.vocab_size))
        d1 = res1["drift"]
        assert d1["tracked_keys"] >= 1 and d1["flagged"] == 0

        # Run 2 (200ms injected into every paged_decode launch, inside
        # the dispatch-timing window): sustained regression -> flag ->
        # synchronous on_drift -> retune enqueued, awaiting the daemon.
        plan = fault_lib.FaultPlan.parse_spec("slow@64:200:paged_decode")
        with fault_lib.active(plan):
            res2 = eng.run(_seeded_reqs(np.random.default_rng(12),
                                        cfg.vocab_size))
        d2 = res2["drift"]
        assert d2["flagged"] >= 1 and d2["retunes"] >= 1
        assert d2["pending_retunes"] >= 1 and d2["flagged_keys"] >= 1
        assert any(l["fault"] == "slowdown" for l in plan.log)
        assert tuner.stats()["drift_retunes"] >= 1
        assert len(tuner.queue) >= 1

        # The background daemon (flushed inline for determinism) retunes
        # the drifted scenario and admits the winner into the portfolio.
        assert tuner.flush_tuning_queue() >= 1
        st = tuner.stats()
        assert st["tunes"] >= 1 and st["portfolio_updates"] >= 1
        assert pf.counts()["members"] >= 1

        # Run 3 (clean): the engine notices the fresher cache entry,
        # re-jits once, clears the pending set, and resets the detector
        # key so the new config calibrates its own baseline.
        res3 = eng.run(_seeded_reqs(np.random.default_rng(13),
                                    cfg.vocab_size))
        d3 = res3["drift"]
        assert d3["rejits"] >= 1
        assert d3["pending_retunes"] == 0 and d3["flagged_keys"] == 0

        # Subsequent dispatches serve the freshly tuned winner — and the
        # live portfolio's selector tracks the same config.
        ctx, used = tuner.last_dispatch("paged_decode")
        from repro.kernels.registry import get_kernel
        kernel = get_kernel("paged_decode").tunable
        entry = tuner.cache.get_raw(kernel.name, kernel.version,
                                    kernel.space, ctx)
        assert entry is not None and used == entry.config
        assert pf.select(kernel, ctx) == entry.config

        # Measured-vs-shipped drift counters surface in the registry too.
        prov = reg.snapshot()["providers"]["drift"]
        assert prov["flagged"] >= 1 and prov["retunes"] >= 1
        assert prov["rejits"] >= 1
    finally:
        tuner_mod.set_default_tuner(None)
