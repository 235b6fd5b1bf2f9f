"""The Autotuner — JIT autotuning with persistent reuse and off-critical-path
tuning, the paper's core mechanism plus its four Q4 fixes.

A ``TunableKernel`` bundles everything the tuner needs:
  * ``space``        — ConfigSpace (Q4.1 API),
  * ``workload_fn``  — config → KernelWorkload for the analytical backend,
  * ``make_runner``  — (config, ctx) → zero-arg callable for wall-clock
                       backends (interpret-mode Pallas / jitted XLA),
  * ``heuristic``    — optional untuned default (the "vendor heuristic"
                       baseline the paper compares against).

Kernels are usually resolved through the kernel registry
(``repro.kernels.registry``): ``tune``/``best_config`` accept either a
``TunableKernel`` or a registered kernel *name*, so callers can say
``tuner.best_config("mla_decode", ctx)`` without importing kernel modules.

``Autotuner.best_config`` is the JIT entry point used by kernels' ops.py at
call time:

  cache hit (env fingerprint + constraints still valid)  → reuse   (Q4.3)
  miss, portfolio attached (config_source "db")          → serve the
                                                           portfolio member,
                                                           enqueue background
                                                           tuning
  miss, policy "tune"                                    → tune now (paper's
                                                           JIT autotuning)
  miss, policy "heuristic"                               → return default,
                                                           enqueue background
                                                           tuning      (Q4.4)
  miss, policy "error"                                   → raise (CI mode)

Under ``config_source="portfolio"`` the "A Few Fit Most" portfolio
(core/portfolio.py) is consulted *before* the point DB: portfolio member →
shipped point entry → heuristic → background tune. Drift-triggered online
retuning closes the loop: ``enable_drift_retune`` re-enqueues flagged cache
keys and ``tune`` admits each fresh winner into the live portfolio.

A persisted *failed* search (metric=inf) is never served as a hit — it is
kept only for visibility, and lookups treat it as a miss so the scenario is
retuned (policy "tune") or re-enqueued (policy "heuristic").

Searches run through the pipelined ``TuningEngine`` (compile/measure
overlap + lowered-HLO dedupe) whenever the backend supports the split;
``tune_many`` tunes independent (kernel, ctx) pairs concurrently on a
thread pool sharing one compile pool, and ``start_background_tuning``
spawns the daemon worker that drains the ``TuningQueue`` during idle time
so ``on_miss="heuristic"`` converges in serving (wired by launch/serve.py).

The module-level ``default_tuner()`` tunes with the analytical backend for
``hardware.default_chip()``: the attached chip on a TPU backend (keyed by
``device_kind``), ``$REPRO_TARGET_CHIP`` (default tpu_v5e) on a CPU host, so
model code autotunes deterministically; tests and benchmarks construct
explicit tuners with wall-clock backends.
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import logging
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Callable, Dict, Iterable, List, Optional, Tuple, Union

from repro.core import cache as cache_lib
from repro.core import engine as engine_lib
from repro.core import measure as measure_lib
from repro.core import search as search_lib
from repro.core.config_space import Config, ConfigSpace, TuningContext
from repro.core.costmodel import KernelWorkload
from repro.core.hardware import default_chip
from repro.obs import trace as trace_lib

log = logging.getLogger("repro.tuner")

# Counter key -> trace instant name on the "tuner" track (obs/trace.py).
_TRACE_NAMES = {
    "hits": "cache_hit", "misses": "cache_miss", "tunes": "tuned",
    "heuristic_uses": "heuristic", "background_tunes": "background_tune",
    "failed_retunes": "failed_retune", "quarantines": "quarantine",
    "fallback_serves": "fallback", "portfolio_serves": "portfolio",
    "portfolio_updates": "portfolio_update", "drift_retunes": "drift_retune",
}

# Bound on the dispatch-key reverse index (cache key -> (kernel, ctx)) that
# lets drift retuning turn a flagged key string back into a tunable request.
_KEY_INDEX_MAX = 512


@dataclasses.dataclass
class TunableKernel:
    name: str
    space: ConfigSpace
    version: int = 1
    workload_fn: Optional[Callable[[Config, TuningContext], KernelWorkload]] = None
    make_runner: Optional[measure_lib.RunnerFactory] = None
    heuristic: Optional[Callable[[TuningContext], Config]] = None
    # Optional map config -> *effective* config (blocks clamped to dims,
    # no-op flags normalized away). Configs with equal canonical forms lower
    # to identical programs ("A Few Fit Most"), so the pipelined engine
    # skips tracing, compiling, and measuring them entirely.
    canonicalize: Optional[Callable[[Config, TuningContext], Config]] = None

    def default_config(self, ctx: TuningContext) -> Config:
        if self.heuristic is not None:
            cfg = self.heuristic(ctx)
            if self.space.is_valid(cfg, ctx):
                return cfg
        return self.space.default(ctx)


class TuningQueue:
    """Deferred tuning requests (paper Q4.4: tune during idle time)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._items: Dict[str, Tuple[TunableKernel, TuningContext]] = {}
        self._nonempty = threading.Event()

    def add(self, kernel: TunableKernel, ctx: TuningContext) -> None:
        key = cache_lib.cache_key(kernel.name, kernel.version, kernel.space, ctx)
        with self._lock:
            self._items.setdefault(key, (kernel, ctx))
            self._nonempty.set()

    def pop(self) -> Optional[Tuple[TunableKernel, TuningContext]]:
        """Remove and return one deferred request, or None when empty."""
        with self._lock:
            if not self._items:
                self._nonempty.clear()
                return None
            key = next(iter(self._items))
            item = self._items.pop(key)
            if not self._items:
                self._nonempty.clear()
            return item

    def drain(self) -> List[Tuple[TunableKernel, TuningContext]]:
        with self._lock:
            items = list(self._items.values())
            self._items.clear()
            self._nonempty.clear()
        return items

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the queue is non-empty (or timeout). True if items
        may be available."""
        return self._nonempty.wait(timeout)

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


KernelRef = Union[TunableKernel, str]


class Autotuner:
    def __init__(self,
                 cache: Optional[cache_lib.TuningCache] = None,
                 backend: Optional[measure_lib.MeasureBackend] = None,
                 strategy: Optional[search_lib.SearchStrategy] = None,
                 on_miss: str = "tune",
                 compile_workers: Optional[int] = None,
                 portfolio=None,
                 config_source: str = "db"):
        assert on_miss in ("tune", "heuristic", "error")
        assert config_source in ("db", "portfolio", "tune")
        self.cache = cache if cache is not None else cache_lib.TuningCache()
        self.backend = backend or measure_lib.AnalyticalMeasure(
            default_chip())
        self.strategy = strategy or search_lib.ExhaustiveSearch()
        self.on_miss = on_miss
        # "A Few Fit Most" portfolio (core/portfolio.py). config_source:
        #   "db"        — point entries first; portfolio consulted on cache
        #                 miss before the heuristic/tune fallback.
        #   "portfolio" — portfolio first, point entries as fallback (the
        #                 small-artifact operating mode).
        #   "tune"      — never consult the portfolio even when attached.
        self.portfolio = portfolio
        self.config_source = config_source
        self.queue = TuningQueue()
        self.engine = engine_lib.TuningEngine(
            self.backend,
            pool=(measure_lib.CompilePool(compile_workers)
                  if compile_workers else None))
        self._stats = {"hits": 0, "misses": 0, "tunes": 0, "heuristic_uses": 0,
                       "background_tunes": 0, "failed_retunes": 0,
                       "quarantines": 0, "fallback_serves": 0,
                       "portfolio_serves": 0, "portfolio_updates": 0,
                       "drift_retunes": 0}
        self._per_kernel: Dict[str, Dict[str, int]] = {}
        self._stats_lock = threading.Lock()
        # Last (ctx, config) served per kernel name: the serving engine's
        # non-finite guard quarantines through this — under jit the
        # dispatch happened at trace time, long before NaNs surface.
        self._last_dispatch: Dict[
            str, Tuple[TuningContext, Config]] = {}
        # Reverse index cache-key -> (kernel, ctx), fed by dispatch_key:
        # drift detectors report flagged *keys*, and retune_key needs the
        # tuning request back. Bounded LRU.
        self._key_index: "collections.OrderedDict[str, Tuple[TunableKernel, TuningContext]]" = (
            collections.OrderedDict())
        self._bg_thread: Optional[threading.Thread] = None
        self._bg_stop = threading.Event()

    def _bump(self, key: str, n: int = 1,
              kernel: Optional[str] = None) -> None:
        with self._stats_lock:
            self._stats[key] += n
            if kernel is not None:
                per = self._per_kernel.setdefault(
                    kernel, {"hits": 0, "misses": 0, "tunes": 0,
                             "background_tunes": 0})
                per[key] = per.get(key, 0) + n
        # Every counter bump doubles as a trace instant on the tuner
        # track (no-op when no tracer is installed).
        trace_lib.active_instant(_TRACE_NAMES.get(key, key), track="tuner",
                                 kernel=kernel)

    def stats(self) -> Dict[str, object]:
        """Snapshot of the tuning counters, including per-kernel cache
        hit/miss/tune counts under ``"per_kernel"`` — the serving benchmark
        reads these to report how quickly tuning cost amortizes (one miss,
        then hits for the rest of the trace)."""
        with self._stats_lock:
            out: Dict[str, object] = dict(self._stats)
            out["per_kernel"] = {k: dict(v)
                                 for k, v in self._per_kernel.items()}
            return out

    # -- core API ----------------------------------------------------------
    @staticmethod
    def resolve(kernel: KernelRef) -> TunableKernel:
        """Accept a TunableKernel or a registry name (registry-driven
        construction: the registry is the only kernel enumeration point)."""
        if isinstance(kernel, str):
            from repro.kernels.registry import get_kernel
            return get_kernel(kernel).tunable
        return kernel

    def tune(self, kernel: KernelRef, ctx: TuningContext,
             strategy: Optional[search_lib.SearchStrategy] = None,
             *, pipelined: Optional[bool] = None) -> cache_lib.CacheEntry:
        """Run the search now and persist the winner. ``kernel`` may be a
        TunableKernel or a registered kernel name.

        ``pipelined=None`` (default) uses the compile/measure-overlap engine
        whenever the backend supports it; ``False`` forces the serial
        evaluate-one-at-a-time path (the benchmark baseline). Strategies are
        stateful, so the tuner always searches on a private clone — one
        strategy instance can serve concurrent ``tune_many`` workers.
        """
        kernel = self.resolve(kernel)
        strat = copy.deepcopy(strategy or self.strategy)
        if pipelined is None:
            pipelined = self.engine.can_pipeline(kernel)
        with trace_lib.active_span("tune", track="tuner",
                                   kernel=kernel.name,
                                   pipelined=bool(pipelined)):
            if pipelined:
                result = self.engine.search(kernel, ctx, strat)
            else:
                result = strat.run(kernel.space, ctx,
                                   self.backend.evaluator(kernel, ctx))
        self._bump("tunes", kernel=kernel.name)
        # Quarantined configs survive re-tunes: a config that failed at
        # serve time must never win again just because it *measures* fine.
        prior = self.cache.get_raw(kernel.name, kernel.version,
                                   kernel.space, ctx)
        quarantined = list(prior.quarantined) if prior is not None else []
        winner, winner_metric, runners_up = _select_clean(result, quarantined)
        if winner is None:
            # Nothing measurable — fall back to the structural default but
            # record the failure so it is visible, not silent.
            cfg = kernel.default_config(ctx)
            entry = cache_lib.make_entry(
                cfg, float("inf"), result.evaluations,
                f"{strat.name}(failed)", self.backend.name,
                _chip_name(self.backend),
                compile_s=result.compile_s, measure_s=result.measure_s)
        else:
            entry = cache_lib.make_entry(
                winner, winner_metric, result.evaluations,
                strat.name, self.backend.name, _chip_name(self.backend),
                compile_s=result.compile_s, measure_s=result.measure_s)
            entry.runners_up = runners_up
        entry.quarantined = quarantined
        self.cache.put(kernel.name, kernel.version, kernel.space, ctx, entry)
        if self.portfolio is not None and winner is not None:
            # Online portfolio update: the fresh winner becomes a member
            # (under the same quarantine/runner-up machinery — quarantined
            # configs were already excluded by _select_clean above) and the
            # scenario's feature signature points at it, so portfolio-first
            # serving picks up the retuned config without a restart.
            if self.portfolio.admit(kernel, ctx, entry.config, entry.metric):
                self._bump("portfolio_updates", kernel=kernel.name)
        log.info("tuned %s ctx=%s -> %s (%.3g s/call, %d evals, "
                 "compile %.2fs / measure %.2fs)",
                 kernel.name, ctx.signature(), entry.config, entry.metric,
                 entry.n_evaluated, entry.compile_s, entry.measure_s)
        return entry

    def tune_many(self, items: Iterable[Tuple[KernelRef, TuningContext]],
                  strategy: Optional[search_lib.SearchStrategy] = None,
                  max_workers: Optional[int] = None,
                  return_exceptions: bool = False,
                  timeout_s: Optional[float] = None,
                  retries: int = 0
                  ) -> List[Union[cache_lib.CacheEntry, BaseException]]:
        """Tune independent (kernel, ctx) pairs concurrently.

        Results align with the input order. Compiles from all searches share
        the engine's pool (and its program cache); device timing interleaves
        fairly under the process-wide device lock; cache writes are
        serialized by the TuningCache lock. With ``return_exceptions`` a
        failing pair yields its exception instead of aborting the batch.

        A hostile config can never kill the batch: a pair that keeps
        raising after ``retries`` extra attempts records a failed
        (metric=inf) cache entry — visible, never served — and the rest of
        the batch completes. ``timeout_s`` is a *soft* per-pair deadline:
        a pair still tuning after it yields ``TimeoutError`` (and the
        failed marker) while its worker thread is left to finish in the
        background — Python threads cannot be killed.
        """
        pairs = [(self.resolve(k), ctx) for k, ctx in items]
        if not pairs:
            return []
        # Each search already keeps ~2 cores busy (lowering + a compile
        # worker), so the default packs one search per core pair.
        workers = max_workers or min(len(pairs),
                                     max(1, (os.cpu_count() or 2) // 2))

        def mark_failed(pair, label):
            kernel, ctx = pair
            entry = cache_lib.make_entry(
                kernel.default_config(ctx), float("inf"), 0, label,
                self.backend.name, _chip_name(self.backend))
            self.cache.put(kernel.name, kernel.version, kernel.space, ctx,
                           entry)

        def one(pair):
            last: Optional[BaseException] = None
            for _ in range(max(1, retries + 1)):
                try:
                    return self.tune(pair[0], pair[1], strategy)
                except Exception as e:      # noqa: BLE001 — isolate pairs
                    last = e
                    log.warning("tune_many: %s failed (%s), %s",
                                pair[0].name, e,
                                "retrying" if retries else "giving up")
            mark_failed(pair, "error")
            raise last

        out: List[Union[cache_lib.CacheEntry, BaseException]] = []
        with ThreadPoolExecutor(max_workers=workers,
                                thread_name_prefix="repro-tune") as ex:
            futures = [ex.submit(one, p) for p in pairs]
            for f, pair in zip(futures, pairs):
                try:
                    out.append(f.result(timeout=timeout_s))
                except FuturesTimeoutError:
                    mark_failed(pair, "timeout")
                    e = TimeoutError(
                        f"tuning {pair[0].name} exceeded {timeout_s}s")
                    if not return_exceptions:
                        raise e from None
                    out.append(e)
                except Exception as e:
                    if not return_exceptions:
                        raise
                    out.append(e)
        return out

    def attach_portfolio(self, portfolio, source: Optional[str] = None
                         ) -> None:
        """Install a config portfolio (core/portfolio.py) and optionally
        switch the lookup precedence (``config_source``). Freshly tuned
        winners are admitted into it from here on — the online half of
        drift-triggered retuning."""
        if source is not None:
            assert source in ("db", "portfolio", "tune")
            self.config_source = source
        self.portfolio = portfolio

    def _portfolio_lookup(self, kernel: TunableKernel,
                          ctx: TuningContext) -> Optional[Config]:
        """The portfolio member for (kernel, ctx), quarantine-aware: a
        member that failed at serve time is excluded exactly like a cached
        winner would be, degrading to the next member and then to the
        caller's fallback chain."""
        if self.portfolio is None:
            return None
        raw = self.cache.get_raw(kernel.name, kernel.version,
                                 kernel.space, ctx)
        quarantined = list(raw.quarantined) if raw is not None else []
        cfg = self.portfolio.select(kernel, ctx, exclude=quarantined)
        if cfg is None:
            return None
        self._bump("portfolio_serves", kernel=kernel.name)
        return cfg

    def best_config(self, kernel: KernelRef, ctx: TuningContext) -> Config:
        kernel = self.resolve(kernel)
        if self.config_source == "portfolio":
            # Portfolio-first: serve the small multi-versioned artifact,
            # fall through to the point DB only when no member may legally
            # serve this scenario ("A Few Fit Most" operating mode).
            cfg = self._portfolio_lookup(kernel, ctx)
            if cfg is not None:
                return cfg
        entry = self.cache.get(
            kernel.name, kernel.version, kernel.space, ctx,
            require_fingerprint={"backend": self.backend.name})
        if entry is not None and entry.failed():
            # Stored failed-search marker: count the forced retune, then
            # fall through to the miss path (never serve it).
            self._bump("failed_retunes", kernel=kernel.name)
            entry = None
        if entry is not None and entry.is_quarantined(entry.config):
            # The winner failed at serve time: degrade to the best
            # runner-up still standing rather than go down (the "A Few
            # Fit Most" portfolio as a fault-tolerance mechanism).
            for ru in entry.runners_up:
                cfg = dict(ru["config"])
                if (not entry.is_quarantined(cfg)
                        and kernel.space.is_valid(cfg, ctx)):
                    self._bump("fallback_serves", kernel=kernel.name)
                    return cfg
            entry = None              # nothing clean left: treat as miss
        if entry is not None:
            self._bump("hits", kernel=kernel.name)
            return dict(entry.config)
        self._bump("misses", kernel=kernel.name)
        if self.config_source == "db":
            # Point-entry miss: consult the portfolio BEFORE the
            # heuristic/tune fallback — a clustered near-optimum beats a
            # vendor default — while still enqueueing a background tune so
            # the cache converges to the point-tuned winner off the
            # critical path.
            cfg = self._portfolio_lookup(kernel, ctx)
            if cfg is not None:
                self.queue.add(kernel, ctx)
                return cfg
        if self.on_miss == "tune":
            return dict(self.tune(kernel, ctx).config)
        if self.on_miss == "heuristic":
            self.queue.add(kernel, ctx)
            self._bump("heuristic_uses", kernel=kernel.name)
            cfg = kernel.default_config(ctx)
            raw = self.cache.get_raw(kernel.name, kernel.version,
                                     kernel.space, ctx)
            if raw is not None and raw.is_quarantined(cfg):
                # The heuristic itself failed at serve time: degrade to
                # the first clean fallback rather than re-serve it.
                for alt in self.fallback_configs(kernel, ctx, exclude=[cfg]):
                    self._bump("fallback_serves", kernel=kernel.name)
                    return alt
            return cfg
        raise LookupError(
            f"no tuned config for kernel {kernel.name!r} ctx {ctx.signature()} "
            f"and on_miss='error'")

    # -- serve-time failure handling ----------------------------------------
    def record_dispatch(self, name: str, ctx: TuningContext,
                        config: Config) -> None:
        """Note the config a kernel entry point is about to launch with
        (called by ops.py on the tuner path) so non-finite output detected
        later — possibly outside jit — can be attributed and quarantined."""
        with self._stats_lock:
            self._last_dispatch[name] = (ctx, dict(config))

    def last_dispatch(self, name: str
                      ) -> Optional[Tuple[TuningContext, Config]]:
        with self._stats_lock:
            return self._last_dispatch.get(name)

    def dispatch_key(self, kernel: KernelRef, ctx: TuningContext
                     ) -> Tuple[str, Optional[float]]:
        """The tuning-cache key for (kernel, ctx) plus the cached entry's
        recorded metric (None when untuned). This is the identity drift
        tracking (obs/drift.py) samples against: a flagged key names
        exactly the DB row online retuning should revisit."""
        kernel = self.resolve(kernel)
        key = cache_lib.cache_key(kernel.name, kernel.version,
                                  kernel.space, ctx)
        with self._stats_lock:
            # Remember how to turn this key back into a tuning request:
            # when drift flags it, retune_key re-enqueues the scenario.
            self._key_index[key] = (kernel, ctx)
            self._key_index.move_to_end(key)
            while len(self._key_index) > _KEY_INDEX_MAX:
                self._key_index.popitem(last=False)
        raw = self.cache.get_raw(kernel.name, kernel.version,
                                 kernel.space, ctx)
        shipped = None
        if raw is not None and math.isfinite(raw.metric):
            shipped = float(raw.metric)
        return key, shipped

    def lookup_key(self, key: str
                   ) -> Optional[Tuple[TunableKernel, TuningContext]]:
        """The (kernel, ctx) behind a cache key previously seen by
        ``dispatch_key`` (None once evicted from the bounded index)."""
        with self._stats_lock:
            return self._key_index.get(key)

    def retune_key(self, key: str) -> bool:
        """Enqueue a background retune for a drift-flagged cache key —
        the production path behind ``DriftDetector.on_drift``. Returns
        False when the key is unknown (never dispatched here)."""
        item = self.lookup_key(key)
        if item is None:
            return False
        kernel, ctx = item
        self.queue.add(kernel, ctx)
        self._bump("drift_retunes", kernel=kernel.name)
        log.warning("drift flagged %s (ctx=%s): background retune enqueued",
                    kernel.name, ctx.signature())
        return True

    def enable_drift_retune(self, det) -> None:
        """Subscribe this tuner's retune path to a DriftDetector: every
        flagged key is re-enqueued for background tuning, and (when a
        portfolio is attached) the fresh winner is admitted into the live
        portfolio by ``tune``."""
        det.on_drift(lambda key, _report: self.retune_key(key))

    def quarantine(self, kernel: KernelRef, ctx: TuningContext,
                   config: Config) -> bool:
        """Mark ``config`` as failed-at-serve-time for (kernel, ctx): it
        is never served again (the marker survives re-tunes), and a
        background re-tune is enqueued so the scenario converges back to
        a measured winner. Returns True if newly quarantined."""
        kernel = self.resolve(kernel)
        entry = self.cache.get_raw(kernel.name, kernel.version,
                                   kernel.space, ctx)
        if entry is None:
            # No entry yet (e.g. heuristic default failed): record a
            # failed marker carrying the quarantine so tune() preserves it.
            entry = cache_lib.make_entry(
                dict(config), float("inf"), 0, "quarantine",
                self.backend.name, _chip_name(self.backend))
        if entry.is_quarantined(config):
            self.queue.add(kernel, ctx)
            return False
        entry.quarantined.append(dict(config))
        self.cache.put(kernel.name, kernel.version, kernel.space, ctx, entry)
        self._bump("quarantines", kernel=kernel.name)
        self.queue.add(kernel, ctx)
        log.warning("quarantined %s config %s (ctx=%s)", kernel.name,
                    config, ctx.signature())
        return True

    def quarantine_last(self, name: str) -> bool:
        """Quarantine the most recently dispatched config of kernel
        ``name`` (the engine's non-finite guard: by the time NaNs surface
        from a jitted step, the dispatch is long gone)."""
        item = self.last_dispatch(name)
        if item is None:
            return False
        ctx, config = item
        return self.quarantine(name, ctx, config)

    def fallback_configs(self, kernel: KernelRef, ctx: TuningContext,
                         exclude: Iterable[Config] = ()) -> List[Config]:
        """Degraded-mode candidates for (kernel, ctx), best first: cached
        runners-up, then attached-portfolio members, then the heuristic
        default — minus anything quarantined or excluded. The reference
        oracle impl is the caller's last resort after these."""
        kernel = self.resolve(kernel)
        bad = {cache_lib.config_key(c) for c in exclude}
        entry = self.cache.get_raw(kernel.name, kernel.version,
                                   kernel.space, ctx)
        out: List[Config] = []
        if entry is not None:
            bad |= {cache_lib.config_key(c) for c in entry.quarantined}
            for ru in entry.runners_up:
                cfg = dict(ru["config"])
                key = cache_lib.config_key(cfg)
                if key not in bad and kernel.space.is_valid(cfg, ctx):
                    out.append(cfg)
                    bad.add(key)
        if self.portfolio is not None and self.config_source != "tune":
            # Portfolio members widen the degraded-mode chain: clustered
            # near-optima are better fallbacks than the vendor default.
            for cfg in self.portfolio.members(kernel.name):
                key = cache_lib.config_key(cfg)
                if key not in bad and kernel.space.is_valid(cfg, ctx):
                    out.append(cfg)
                    bad.add(key)
        default = kernel.default_config(ctx)
        if cache_lib.config_key(default) not in bad:
            out.append(default)
        return out

    # -- off-critical-path tuning (Q4.4) -----------------------------------
    def flush_tuning_queue(self) -> int:
        """Tune everything deferred by the heuristic policy (idle-time hook)."""
        items = self.queue.drain()
        for kernel, ctx in items:
            self.tune(kernel, ctx)
        return len(items)

    def start_background_tuning(self, poll_interval_s: float = 0.25
                                ) -> threading.Thread:
        """Start (idempotently) the daemon worker that drains the
        TuningQueue whenever items appear, so serving under
        ``on_miss="heuristic"`` converges to tuned configs without ever
        blocking the request path."""
        if self._bg_thread is not None and self._bg_thread.is_alive():
            return self._bg_thread
        # Each worker owns its stop event: if a previous worker outlived its
        # join timeout (stuck in a slow tune), its event stays set and it
        # exits on its own — a fresh event can't accidentally revive it.
        stop = threading.Event()
        self._bg_stop = stop

        def worker():
            while not stop.is_set():
                if not self.queue.wait(timeout=poll_interval_s):
                    continue
                item = self.queue.pop()
                if item is None:
                    continue
                kernel, ctx = item
                try:
                    self.tune(kernel, ctx)
                    self._bump("background_tunes", kernel=kernel.name)
                except Exception:
                    log.exception("background tuning failed for %s",
                                  kernel.name)

        self._bg_thread = threading.Thread(
            target=worker, name="repro-bg-tuner", daemon=True)
        self._bg_thread.start()
        return self._bg_thread

    def stop_background_tuning(self, timeout: float = 10.0) -> None:
        if self._bg_thread is None:
            return
        self._bg_stop.set()
        self._bg_thread.join(timeout)
        if self._bg_thread.is_alive():
            log.warning("background tuner still finishing a tune after "
                        "%.1fs; it will exit when the tune completes", timeout)
        self._bg_thread = None

    def close(self) -> None:
        """Release the engine's compile pool and stop the background
        worker. Process-lifetime tuners (default_tuner) never need this;
        short-lived tuners in tests/benchmarks do."""
        self.stop_background_tuning()
        self.engine.close()


def _select_clean(result: search_lib.SearchResult,
                  quarantined: List[Config]
                  ) -> Tuple[Optional[Config], float, List[Dict]]:
    """Pick the best non-quarantined finite trial as the winner and the
    next-best distinct configs (up to 3) as the runner-up portfolio."""
    bad = {cache_lib.config_key(c) for c in quarantined}
    ranked: List[Tuple[str, Config, float]] = []
    seen = set()
    for t in sorted(result.trials, key=lambda t: t.metric):
        if not math.isfinite(t.metric):
            continue
        key = cache_lib.config_key(t.config)
        if key in bad or key in seen:
            continue
        seen.add(key)
        ranked.append((key, dict(t.config), float(t.metric)))
    if not ranked:
        return None, math.inf, []
    _, winner, winner_metric = ranked[0]
    runners_up = [{"config": cfg, "metric": m}
                  for _, cfg, m in ranked[1:4]]
    return winner, winner_metric, runners_up


def _chip_name(backend: measure_lib.MeasureBackend) -> str:
    chip = getattr(backend, "chip", None)
    if chip is not None:
        return chip.name
    analytical = getattr(backend, "analytical", None)
    if analytical is not None:
        return analytical.chip.name
    return "local"


# ---------------------------------------------------------------------------
# Process-wide default tuner used by kernels/ops.py at call sites.
# ---------------------------------------------------------------------------
_DEFAULT: Optional[Autotuner] = None
_DEFAULT_LOCK = threading.Lock()

SHIPPED_DB = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, "configs",
    "shipped_tuning_db.json"))


def default_tuner() -> Autotuner:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = Autotuner(
                cache=cache_lib.TuningCache(overlay_path=SHIPPED_DB),
                on_miss=os.environ.get("REPRO_ON_MISS", "tune"),
            )
            # Opt-in config-portfolio serving (launch/serve.py
            # --config-source): attach the shipped portfolio artifact and
            # set the lookup precedence. Unset/"tune" keeps the point-DB
            # behavior byte-identical.
            source = os.environ.get("REPRO_CONFIG_SOURCE", "")
            if source in ("db", "portfolio"):
                from repro.core.portfolio import Portfolio
                pf = Portfolio.load_shipped()
                if pf is not None:
                    _DEFAULT.attach_portfolio(pf, source=source)
            if (_DEFAULT.on_miss == "heuristic"
                    and os.environ.get("REPRO_BG_TUNING", "0") == "1"):
                _DEFAULT.start_background_tuning(
                    float(os.environ.get("REPRO_BG_INTERVAL", "0.25")))
        return _DEFAULT


def set_default_tuner(tuner: Optional[Autotuner]) -> None:
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = tuner
