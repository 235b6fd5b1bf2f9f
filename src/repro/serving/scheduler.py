"""Continuous-batching scheduler + serving engine.

Every scheduler step:

  1. **retire**  — sequences that hit their generation budget free their
                   pages back to the pool (recycled for waiting requests),
  2. **admit**   — the lifecycle sweep fails cancelled/expired requests,
                   then waiting requests claim free batch slots under
                   *optimistic* admission: only the prompt's pages are
                   reserved, never the worst-case generation length,
  3. **prefill** — ONE pending sequence runs one fixed-width prompt chunk
                   (chunked prefill: long prompts never monopolize a step),
  4. **decode**  — every prefilled, unfinished sequence decodes one token
                   through the autotuned ``paged_decode`` kernel; a slot
                   that outgrows its pages allocates one more, and on pool
                   exhaustion a victim (latest arrival first) is preempted
                   and re-queued.

``ServingEngine.step`` records each phase as a ``serve.*`` span in the
profiler's trace, and the scheduler stamps each ``Request`` with its
submission, admission and prompt completion (docs/observability.md).

Optimistic admission is what makes the pool a real resource: admission no
longer reserves ``prompt + max_new_tokens`` pages up front, so many more
requests run concurrently, and the price is that the pool can exhaust
mid-flight. Preemption pays that price deterministically: the victim's
resident pages are parked in the ``PrefixCache`` trie (when one is
attached) or freed, the request re-queues with bounded backoff, and on
resume it re-prefills ``prompt + tokens[:-1]`` — exactly the KV it had
resident (the last generated token was never written) — so a resumed
request produces **token-for-token the same output** as an uninterrupted
run under greedy sampling.

Every ``Request`` carries a lifecycle state machine (QUEUED → RUNNING ⇄
PREEMPTED → FINISHED / FAILED / TIMED_OUT): oversized submissions become
FAILED results instead of exceptions, deadlines and cancellation are
enforced in the step loop, and a request preempted more than
``max_retries`` times fails rather than thrash forever.

The ``Scheduler`` is pure host-side bookkeeping over a ``PagePool`` (no
jax imports): block tables and lengths are numpy arrays the property tests
can drive with random admit/finish/preempt traces. ``ServingEngine`` binds
a model to it and runs the jitted ``lm.prefill_paged`` /
``lm.decode_step_paged`` steps with greedy sampling, plus a non-finite
guard on the decode logits (NaN logits fail the request and quarantine the
active ``paged_decode`` config instead of emitting garbage argmax tokens).

With ``speculative=K`` the engine swaps the one-token decode step for
draft-and-verify: a per-request n-gram drafter (serving/drafter.py)
proposes K-1 continuation tokens, ``lm.verify_step_paged`` scores all K
positions in one autotuned ``paged_verify`` launch, and the scheduler
commits the greedily-matched prefix (1..K tokens per step), rolling back
pages reserved for the rejected tail. Greedy accept/rollback keeps output
token-for-token identical to plain decode; verify faults degrade the
engine to non-speculative decode instead of failing requests.
"""

from __future__ import annotations

import dataclasses
import enum
import math
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.obs import drift as drift_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.serving import faults as fault_lib
from repro.serving.page_pool import SCRATCH_PAGE, PagePool
from repro.serving.prefix_cache import PrefixCache

# Per-request token-timestamp cap: past this many samples new timestamps
# are counted in ``token_times_dropped`` instead of appended, so latency
# bookkeeping on a long-running request stays O(1) memory. Percentiles in
# the run report are computed over the recorded sample prefix.
TOKEN_TIMES_CAP = 4096


class RequestState(str, enum.Enum):
    """Request lifecycle. QUEUED → RUNNING ⇄ PREEMPTED, terminating in
    FINISHED (budget reached), FAILED (rejected / cancelled / non-finite
    logits / retry budget exhausted) or TIMED_OUT (deadline passed)."""

    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    PREEMPTED = "PREEMPTED"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    TIMED_OUT = "TIMED_OUT"


TERMINAL_STATES = (RequestState.FINISHED, RequestState.FAILED,
                   RequestState.TIMED_OUT)


@dataclasses.dataclass
class Request:
    """One inference request."""

    rid: int
    prompt: np.ndarray                 # (P,) int32 token ids
    max_new_tokens: int
    arrival: float = 0.0               # seconds since trace start
    deadline: Optional[float] = None   # absolute trace-clock deadline
    max_retries: int = 8               # preemption/resume budget
    # filled in by the engine:
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_times_dropped: int = 0       # samples past TOKEN_TIMES_CAP
    last_token_time: Optional[float] = None
    state: RequestState = RequestState.QUEUED
    failure_reason: Optional[str] = None
    retries: int = 0                   # times preempted so far
    cancelled: bool = False
    wait_steps: int = 0                # admission aging (head-of-line cap)
    not_before_step: int = 0           # backoff: earliest re-admission step
    # Scheduler spans and counters (``time.perf_counter`` seconds, the
    # clock of ``token_times``), first occupancy only: a resumed request
    # keeps its first stamps and counts nothing more.
    submitted_at: Optional[float] = None   # Scheduler.submit
    admitted_at: Optional[float] = None    # first admission to a slot
    prefilled_at: Optional[float] = None   # its last prompt chunk ran
    prefill_chunks: int = 0            # prompt chunks run for it
    prefill_steps: int = 0             # steps that found prompt tokens left

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))

    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def cancel(self) -> None:
        """Mark for cancellation; the next lifecycle sweep fails it."""
        self.cancelled = True

    def note_token_time(self, t: float) -> None:
        """Record a token timestamp, bounded by ``TOKEN_TIMES_CAP``."""
        self.last_token_time = t
        if len(self.token_times) < TOKEN_TIMES_CAP:
            self.token_times.append(t)
        else:
            self.token_times_dropped += 1


@dataclasses.dataclass
class _Seq:
    """Per-slot state of an admitted sequence."""

    req: Request
    pages: List[int]
    view: np.ndarray                   # tokens to prefill (prompt, or on
    #                                    resume prompt + generated[:-1])
    pos: int = 0                       # resident (written) valid tokens
    prompt_done: bool = False
    cached_tokens: int = 0             # prefix served from the cache


@dataclasses.dataclass
class StepStats:
    admitted: int = 0
    retired: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefix_cached_tokens: int = 0      # prefill tokens avoided this step
    preempted: int = 0                 # sequences preempted this step
    failed: int = 0                    # requests failed this step
    timed_out: int = 0                 # requests expired this step
    degraded: int = 0                  # speculative→plain fallbacks this step

    def progressed(self) -> bool:
        # ``degraded`` counts: a poisoned verify burst commits nothing,
        # but flipping the engine to plain decode IS forward progress —
        # the same positions re-score next step.
        return bool(self.admitted or self.retired or self.prefill_tokens
                    or self.decode_tokens or self.preempted or self.failed
                    or self.timed_out or self.degraded)


def latency_summary(requests: List[Request]) -> Dict[str, Any]:
    """Exact p50/p99 TTFT and inter-token latency (ms) from the recorded
    ``Request.token_times``. TTFT is first token minus the request's own
    ``submitted_at``; inter-token gaps are consecutive-timestamp deltas
    within each request. Percentiles cover the recorded sample prefix —
    ``token_times_dropped`` reports what the ``TOKEN_TIMES_CAP`` bound
    discarded."""
    ttfts: List[float] = []
    itls: List[float] = []
    dropped = 0
    for r in requests:
        ts = r.token_times
        dropped += r.token_times_dropped
        if ts:
            if r.submitted_at is not None:
                ttfts.append((ts[0] - r.submitted_at) * 1e3)
            itls.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))

    def pct(xs: List[float], q: float) -> Optional[float]:
        return float(np.percentile(xs, q)) if xs else None

    return {
        "ttft_p50_ms": pct(ttfts, 50),
        "ttft_p99_ms": pct(ttfts, 99),
        "itl_p50_ms": pct(itls, 50),
        "itl_p99_ms": pct(itls, 99),
        "ttft_samples": len(ttfts),
        "itl_samples": len(itls),
        "token_times_dropped": dropped,
    }


class Scheduler:
    """Slot/page bookkeeping for a continuous batch.

    ``max_batch`` concurrent sequences; each owns up to ``max_pages``
    block-table entries (table width). Unused entries map to the scratch
    page so device-side index maps never branch.

    ``lookahead`` bounds how far past a blocked queue head admission may
    scan for a smaller request that fits (head-of-line fix); once the head
    has been skipped ``aging_cap`` times the scan collapses back to strict
    FIFO until the head admits, so big requests cannot starve.
    """

    def __init__(self, pool: PagePool, max_batch: int, max_pages: int,
                 prefill_chunk: int = 8,
                 prefix_cache: Optional[PrefixCache] = None,
                 lookahead: int = 4, aging_cap: int = 64,
                 record_events: bool = False, spec_k: int = 1,
                 tracer: Optional[trace_lib.Tracer] = None,
                 metrics: Optional[metrics_lib.MetricsRegistry] = None):
        self.pool = pool
        self.max_batch = int(max_batch)
        self.max_pages = int(max_pages)
        self.prefill_chunk = int(prefill_chunk)
        # Speculative verify width: each decode step may scatter up to
        # spec_k draft tokens before any of them is accepted, so capacity
        # checks and the oversized-rejection bound must charge the burst.
        self.spec_k = max(1, int(spec_k))
        self.prefix_cache = prefix_cache
        if prefix_cache is not None and prefix_cache.pool is not pool:
            raise ValueError("prefix cache must index the scheduler's pool")
        self.lookahead = max(1, int(lookahead))
        self.aging_cap = int(aging_cap)
        self.waiting: Deque[Request] = deque()
        self.slots: List[Optional[_Seq]] = [None] * self.max_batch
        self.finished: List[Request] = []
        self._tables = np.full((self.max_batch, self.max_pages),
                               SCRATCH_PAGE, np.int32)
        self._prefill_rr = 0           # round-robin cursor over slots
        self._step = 0                 # admission calls (backoff clock)
        self.total_prefill_tokens = 0  # chunk tokens actually computed
        self.total_cached_tokens = 0   # prefill tokens the cache avoided
        self.preemptions = 0
        self.resumes = 0
        self.failures = 0
        self.timeouts = 0
        self.record_events = bool(record_events)
        self.events: List[Dict[str, Any]] = []
        self.tracer = tracer
        self.metrics = metrics
        self._m_admit_wait = (
            metrics.histogram(
                "serving_admit_wait_ms",
                help="submission to first admission per request (ms)")
            if metrics is not None else None)

    def _event(self, op: str, **kw) -> None:
        if self.record_events:
            self.events.append(dict(op=op, step=self._step, **kw))
        if self.tracer is not None and op not in ("admit", "retire"):
            # admit/retire become lifecycle spans on the slot track;
            # everything else is an instant on the shared lifecycle track.
            self.tracer.instant(op, track="lifecycle", step=self._step, **kw)

    # -- request intake ----------------------------------------------------
    def max_tokens(self, req: Request) -> int:
        """Worst-case resident tokens over the request's whole lifetime,
        including the longest possible chunk-padded *resume* view
        (prompt + max_new_tokens - 1 re-prefilled after a late
        preemption) — the bound the oversized-rejection guard checks.

        Under speculative decoding the burst is charged up front: the
        deepest verify step starts from pos = total - 2 (one committed
        token short of the budget) and scatters spec_k draft positions,
        so total - 2 + spec_k tokens may be resident at once even though
        at most one of those drafts is ever kept."""
        c = self.prefill_chunk
        total = req.prompt_len + req.max_new_tokens
        pad = lambda n: -(-n // c) * c          # noqa: E731
        burst = total - 2 + self.spec_k if self.spec_k > 1 else 0
        return max(pad(req.prompt_len), pad(total - 1), total, burst)

    def _prefill_view(self, req: Request) -> np.ndarray:
        """Tokens to (re-)prefill: the prompt, or on resume the prompt
        plus every generated token but the last — the last token was
        produced but its KV never written, so it re-enters via decode."""
        if req.tokens:
            return np.concatenate(
                [req.prompt,
                 np.asarray(req.tokens[:-1], np.int32)]).astype(np.int32)
        return np.asarray(req.prompt, np.int32)

    def reject(self, req: Request, reason: str) -> None:
        """Complete ``req`` as a FAILED result (never raises): one bad
        request must not abort a whole trace replay."""
        req.state = RequestState.FAILED
        req.failure_reason = reason
        self.failures += 1
        self.finished.append(req)
        self._event("reject", rid=req.rid, reason=reason)

    def submit(self, req: Request) -> None:
        req.submitted_at = time.perf_counter()
        if req.prompt_len < 1 or req.max_new_tokens < 1:
            return self.reject(req, "empty prompt or zero generation budget")
        need = self.pool.pages_for(self.max_tokens(req))
        if need > self.max_pages:
            return self.reject(
                req, f"needs {need} pages > table width {self.max_pages}")
        if need > self.pool.num_pages - 1:
            return self.reject(
                req, f"needs {need} pages > pool capacity "
                     f"{self.pool.num_pages - 1}")
        req.state = RequestState.QUEUED
        self.waiting.append(req)
        self._event("submit", rid=req.rid)

    # -- the four phases ---------------------------------------------------
    def retire_finished(self) -> List[Request]:
        out = []
        for b, seq in enumerate(self.slots):
            if seq is not None and seq.prompt_done and seq.req.done():
                self._release_slot(b, park=True)
                seq.req.state = RequestState.FINISHED
                self.finished.append(seq.req)
                self._event("retire", rid=seq.req.rid,
                            tokens=len(seq.req.tokens))
                out.append(seq.req)
        return out

    def _release_slot(self, b: int, park: bool) -> int:
        """Free slot ``b``'s pages (or park the resident full pages in the
        prefix trie) and clear the slot. Returns pages parked."""
        seq = self.slots[b]
        parked = 0
        if park and self.prefix_cache is not None:
            parked = self._park(seq)
        else:
            self.pool.free(seq.pages)
        self._tables[b, :] = SCRATCH_PAGE
        self.slots[b] = None
        if self.tracer is not None:
            self.tracer.end(f"req{seq.req.rid}", track=f"slot{b}",
                            generated=len(seq.req.tokens))
        return parked

    def _park(self, seq: _Seq) -> int:
        """Retire/preempt through the prefix cache: the sequence's full
        resident pages are parked in the trie under their token ids
        (prompt + generated tokens — the last generated token was never
        written), so the next request with this prefix (including this
        request's own resume) hits instead of re-prefilling; the ragged
        tail and unused reservation are freed.

        Page-boundary accounting: during decode ``pos`` always equals
        ``len(prompt) + len(tokens) - 1`` (mid-prefill it is <= the view
        length), so the resident stream is at least ``pos`` tokens long
        and the ``n_full * ps`` slice below is exact — including when
        ``pos`` lands exactly on a page boundary, where the last
        allocated page holds no valid token yet and is freed, not
        parked. Speculative rollback keeps this true: rejected draft KV
        only ever lives at positions >= pos, i.e. outside every full
        page counted by ``n_full``."""
        ps = self.pool.page_size
        n_full = min(seq.pos // ps, len(seq.pages))
        resident = np.concatenate(
            [seq.req.prompt,
             np.asarray(seq.req.tokens[:-1], np.int32)])[:n_full * ps]
        assert len(resident) == n_full * ps, \
            f"parked slice {len(resident)} != {n_full} full pages of {ps}"
        self.prefix_cache.insert(resident, seq.pages[:n_full],
                                 rid=seq.req.rid)
        self.pool.free(seq.pages[n_full:])
        return n_full

    # -- lifecycle ---------------------------------------------------------
    def _finish_abnormal(self, req: Request, state: RequestState,
                         reason: str) -> None:
        req.state = state
        req.failure_reason = reason
        if state is RequestState.TIMED_OUT:
            self.timeouts += 1
        else:
            self.failures += 1
        self.finished.append(req)
        self._event("fail" if state is RequestState.FAILED else "timeout",
                    rid=req.rid, reason=reason)

    def fail_slot(self, b: int, reason: str) -> None:
        """Abort a running sequence as FAILED (engine non-finite guard).
        Its pages are freed, never parked — NaN KV must not enter the
        prefix trie."""
        seq = self.slots[b]
        assert seq is not None
        self._release_slot(b, park=False)
        self._finish_abnormal(seq.req, RequestState.FAILED, reason)

    def _expired(self, req: Request, now: float) -> Optional[str]:
        if req.cancelled:
            return "cancelled"
        if (req.deadline is not None and math.isfinite(now)
                and now > req.deadline):
            return "deadline"
        return None

    def _sweep_lifecycle(self, now: float) -> None:
        """Enforce cancellation and deadlines on waiting AND running
        requests. ``now=inf`` (untimed replay) checks cancellation only."""
        if self.waiting:
            keep: Deque[Request] = deque()
            for req in self.waiting:
                why = self._expired(req, now)
                if why == "cancelled":
                    self._finish_abnormal(req, RequestState.FAILED,
                                          "cancelled")
                elif why == "deadline":
                    self._finish_abnormal(req, RequestState.TIMED_OUT,
                                          f"deadline {req.deadline} passed")
                else:
                    keep.append(req)
            self.waiting = keep
        for b, seq in enumerate(self.slots):
            if seq is None:
                continue
            why = self._expired(seq.req, now)
            if why is None:
                continue
            self._release_slot(b, park=False)
            if why == "cancelled":
                self._finish_abnormal(seq.req, RequestState.FAILED,
                                      "cancelled")
            else:
                self._finish_abnormal(seq.req, RequestState.TIMED_OUT,
                                      f"deadline {seq.req.deadline} passed")

    # -- admission ---------------------------------------------------------
    def admit(self, now: float = float("inf")) -> List[int]:
        """Optimistic admission: a request enters when a slot is free AND
        the pool covers its chunk-padded *prefill view* — never the
        worst-case generation length (decode grows pages on demand and
        preempts under exhaustion).

        Head-of-line blocking fix: when the queue head doesn't fit, up to
        ``lookahead - 1`` later arrivals are tried; after ``aging_cap``
        skips the scan reverts to strict FIFO so the head can't starve.

        With a prefix cache, the cached full-page prefix is share()d
        (refcount bump pins it against eviction) and admission charges
        only the *marginal* pages; under pool pressure, LRU refcount-1
        trie pages are evicted before giving up."""
        self._step += 1
        self._sweep_lifecycle(now)
        admitted = []
        head = self.waiting[0] if self.waiting else None
        for b in range(self.max_batch):
            if self.slots[b] is not None:
                continue
            if self._admit_into(b, now) is None:
                break
            admitted.append(b)
        if (self.waiting and self.waiting[0] is head and head is not None
                and head.arrival <= now
                and head.not_before_step <= self._step):
            head.wait_steps += 1   # an eligible head sat out this step
        return admitted

    def _admit_into(self, b: int, now: float) -> Optional[int]:
        """Try to admit one waiting request into free slot ``b``; returns
        the queue index admitted or None when nothing fits."""
        if not self.waiting:
            return None
        head = self.waiting[0]
        window = 1 if head.wait_steps > self.aging_cap else min(
            self.lookahead, len(self.waiting))
        for i in range(window):
            req = self.waiting[i]
            if req.arrival > now:
                break                  # deque is arrival-ordered
            if req.not_before_step > self._step:
                continue               # preemption backoff
            if self._try_place(b, i):
                return i
        return None

    def _try_place(self, b: int, i: int) -> bool:
        req = self.waiting[i]
        view = self._prefill_view(req)
        c = self.prefill_chunk
        padded = -(-len(view) // c) * c
        need = self.pool.pages_for(padded)
        cached_pages: List[int] = []
        cached_tokens = 0
        if self.prefix_cache is not None:
            # Fresh requests cap the match at prompt_len - 1: at least one
            # prompt token must prefill to produce the first-token logits.
            # Resumes may match the whole view — their next token re-enters
            # through decode, no prefill logits needed.
            limit = len(view) if req.tokens else req.prompt_len - 1
            cached_pages, cached_tokens = self.prefix_cache.match(
                view, limit=limit, rid=req.rid)
            self.pool.share(cached_pages)   # pin before any eviction
            need -= len(cached_pages)
            deficit = need - self.pool.num_free
            if deficit > 0:
                self.prefix_cache.evict(deficit)
        pages = self.pool.alloc(max(0, need))
        if pages is None:
            if cached_pages:
                self.pool.free(cached_pages)   # unpin, retry later
            return False               # pool pressure: wait / look ahead
        del self.waiting[i]
        resumed = req.state is RequestState.PREEMPTED
        req.state = RequestState.RUNNING
        req.wait_steps = 0
        all_pages = cached_pages + pages
        seq = _Seq(req=req, pages=all_pages, view=view,
                   pos=cached_tokens, cached_tokens=cached_tokens)
        if cached_tokens >= len(view):
            # Whole resume view served from the trie: nothing to prefill,
            # decode re-enters with the last generated token.
            assert req.tokens, "fresh match is capped below prompt_len"
            seq.prompt_done = True
        self.slots[b] = seq
        self._tables[b, :] = SCRATCH_PAGE
        self._tables[b, :len(all_pages)] = all_pages
        self.total_cached_tokens += cached_tokens
        if resumed:
            self.resumes += 1
        if self.tracer is not None:
            self.tracer.begin(f"req{req.rid}", track=f"slot{b}",
                              rid=req.rid, resumed=resumed,
                              cached_tokens=cached_tokens,
                              pages=len(all_pages))
        if req.admitted_at is None:
            req.admitted_at = time.perf_counter()
            if self._m_admit_wait is not None and \
                    req.submitted_at is not None:
                self._m_admit_wait.observe(
                    (req.admitted_at - req.submitted_at) * 1e3)
        self._event("admit", rid=req.rid, resumed=resumed,
                    cached_tokens=cached_tokens, pages=len(all_pages))
        return True

    # -- preemption --------------------------------------------------------
    def _reclaim_one(self) -> bool:
        """Free pages by retiring a finished-but-unretired sequence, else
        preempting the latest-arrival running sequence. False when no
        sequence is left to take pages from."""
        for b, seq in enumerate(self.slots):
            if seq is not None and seq.prompt_done and seq.req.done():
                self._release_slot(b, park=True)
                seq.req.state = RequestState.FINISHED
                self.finished.append(seq.req)
                self._event("retire", rid=seq.req.rid,
                            tokens=len(seq.req.tokens))
                return True
        victim = None
        for b, seq in enumerate(self.slots):
            if seq is None:
                continue
            if victim is None or ((seq.req.arrival, seq.req.rid)
                                  > (self.slots[victim].req.arrival,
                                     self.slots[victim].req.rid)):
                victim = b
        if victim is None:
            return False
        self.preempt(victim)
        return True

    def preempt(self, b: int, reason: str = "pool_exhausted") -> None:
        """Evict sequence ``b`` mid-flight: park its resident full pages
        in the prefix trie (restart is then mostly cache hits) or free
        them, and re-queue the request in arrival order with exponential
        step backoff. Exceeding ``max_retries`` preemptions fails the
        request instead of thrashing forever."""
        seq = self.slots[b]
        assert seq is not None
        req = seq.req
        parked = self._release_slot(b, park=True)
        req.state = RequestState.PREEMPTED
        req.retries += 1
        self.preemptions += 1
        self._event("preempt", rid=req.rid, reason=reason,
                    parked_pages=parked, generated=len(req.tokens))
        if req.retries > req.max_retries:
            self._finish_abnormal(
                req, RequestState.FAILED,
                f"preempted {req.retries} times > max_retries "
                f"{req.max_retries}")
            return
        req.not_before_step = self._step + min(
            1 << min(req.retries - 1, 4), 16)
        items = list(self.waiting)
        items.append(req)
        items.sort(key=lambda r: (r.arrival, r.rid))
        self.waiting = deque(items)

    def _ensure_capacity(self, b: int, n: int = 1) -> bool:
        """Grow slot ``b``'s pages to cover its next ``n`` decode writes
        (n = spec_k for a speculative verify burst). On pool exhaustion:
        evict LRU trie pages, then preempt victims (latest arrival first
        — possibly ``b`` itself). False iff ``b`` was preempted."""
        seq = self.slots[b]
        while self.pool.pages_for(seq.pos + n) > len(seq.pages):
            pg = self.pool.alloc(1)
            if (pg is None and self.prefix_cache is not None
                    and self.prefix_cache.evict(1)):
                pg = self.pool.alloc(1)
            if pg is None:
                if not self._reclaim_one():
                    return False       # defensive: nothing left to take
                if self.slots[b] is not seq:
                    return False       # b itself was the victim
                continue
            seq.pages.extend(pg)
            self._tables[b, len(seq.pages) - 1] = pg[0]
        return True

    # -- prefill / decode --------------------------------------------------
    def next_prefill(self) -> Optional[Tuple[int, np.ndarray, int, int]]:
        """Pick one sequence with pending prefill tokens (round-robin) and
        cut its next chunk. Returns (slot, padded chunk (C,), start,
        n_valid) or None. Called once a step: every first-occupancy
        sequence with prompt tokens left counts the step in
        ``Request.prefill_steps``, whether or not its chunk is the one
        cut."""
        c = self.prefill_chunk
        for seq in self.slots:
            if seq is not None and not seq.prompt_done \
                    and seq.req.retries == 0:
                seq.req.prefill_steps += 1
        for off in range(self.max_batch):
            b = (self._prefill_rr + off) % self.max_batch
            seq = self.slots[b]
            if seq is None or seq.prompt_done:
                continue
            self._prefill_rr = (b + 1) % self.max_batch
            start = seq.pos
            chunk = seq.view[start:start + c]
            valid = len(chunk)
            if valid < c:
                chunk = np.concatenate(
                    [chunk, np.zeros(c - valid, np.int32)])
            return b, chunk.astype(np.int32), start, valid
        return None

    def mark_prefilled(self, slot: int, n_valid: int) -> None:
        seq = self.slots[slot]
        assert seq is not None and not seq.prompt_done
        seq.pos += n_valid
        self.total_prefill_tokens += n_valid
        if seq.pos >= len(seq.view):
            seq.prompt_done = True
        if seq.req.retries == 0:
            seq.req.prefill_chunks += 1
            if seq.prompt_done:
                seq.req.prefilled_at = time.perf_counter()

    def decode_mask(self, lookahead: int = 1) -> np.ndarray:
        """Decode-ready slots, after growing every slot's pages to cover
        this step's write — ``lookahead`` tokens of it for a speculative
        verify burst (which may preempt victims — including slots
        already scanned, so readiness is re-derived afterwards)."""
        n = max(1, int(lookahead))
        for b in range(self.max_batch):
            seq = self.slots[b]
            if seq is not None and seq.prompt_done and not seq.req.done():
                self._ensure_capacity(b, n)
        return np.array(
            [s is not None and s.prompt_done and not s.req.done()
             and self.pool.pages_for(s.pos + n) <= len(s.pages)
             for s in self.slots], bool)

    def advance_decoded(self, mask: np.ndarray) -> None:
        for b in np.nonzero(mask)[0]:
            self.slots[int(b)].pos += 1

    def commit_verify(self, b: int, accepted: int) -> None:
        """Commit a speculative verify step for slot ``b``: ``accepted``
        tokens (1..spec_k) were appended to the request, so ``pos``
        advances by that many. The rejected tail's pages are NOT freed:
        they are needed again for the very next burst, and — the bug
        this guards against — a slot's page list must only ever grow
        while it is occupied. The engine caches device block tables
        keyed on (rid, ready, len(pages)); a free-then-regrow can hand
        the page to another slot while the stale device table still
        maps it here, so the next write would corrupt that slot's KV.
        The reservation is already charged by ``max_tokens``'s burst
        bound; preemption and retirement release it like any other
        page. Stale draft KV past ``pos`` is harmless: the next write
        overwrites it, attention never reads past ``kv_len``, and
        ``_park`` only parks full pages below ``pos``."""
        seq = self.slots[b]
        assert seq is not None and 1 <= accepted <= self.spec_k
        seq.pos += accepted

    # -- device-facing state ----------------------------------------------
    def block_tables(self) -> np.ndarray:
        return self._tables.copy()

    def lens(self) -> np.ndarray:
        return np.array([0 if s is None else s.pos for s in self.slots],
                        np.int32)

    # -- progress ----------------------------------------------------------
    def has_work(self) -> bool:
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def backoff_pending(self) -> bool:
        """True when admission is only waiting out preemption backoff —
        the engine's stall detector keeps stepping instead of raising."""
        return any(r.not_before_step > self._step for r in self.waiting)

    def fast_forward_backoff(self) -> bool:
        """Jump the step clock to just before the earliest pending
        ``not_before_step`` so a fully-backed-off queue drains in O(1)
        steps instead of one idle step per backoff tick. Only safe when
        backoff is the *only* pending work (no active slots, no fault
        plan holding pages against a release step) — the engine's run
        loop checks that before calling. Returns True if it jumped."""
        pending = [r.not_before_step for r in self.waiting
                   if r.not_before_step > self._step]
        if not pending:
            return False
        # admit() increments _step before the eligibility check, so
        # landing at (earliest - 1) makes the next admission eligible.
        self._step = min(pending) - 1
        return True

    def check_invariants(self) -> None:
        """Pool consistency + block tables consistent with ownership."""
        self.pool.check_invariants()
        owners: Dict[int, int] = {}
        for b, seq in enumerate(self.slots):
            if seq is None:
                assert (self._tables[b] == SCRATCH_PAGE).all()
                continue
            n = len(seq.pages)
            assert list(self._tables[b, :n]) == seq.pages
            assert (self._tables[b, n:] == SCRATCH_PAGE).all()
            assert seq.pos <= n * self.pool.page_size
            assert len(set(seq.pages)) == n, "page twice in one table"
            assert seq.req.state is RequestState.RUNNING
            for p in seq.pages:
                owners[p] = owners.get(p, 0) + 1
        if self.prefix_cache is None:
            # Without prefix sharing a page belongs to exactly one slot.
            assert all(c == 1 for c in owners.values()), \
                "page mapped to two slots"
        else:
            self.prefix_cache.check_invariants()
        for p, c in owners.items():
            # Every slot mapping is backed by an ownership the pool knows
            # about (shared cache pages count each co-owner).
            assert self.pool.refcount(p) >= c, \
                f"page {p}: {c} slot owners > refcount {self.pool.refcount(p)}"
        for req in self.finished:
            assert req.terminal(), \
                f"request {req.rid} finished in state {req.state}"


class _Both:
    """Enter a profiler annotation and a tracer span as one span."""

    __slots__ = ("ann", "span")

    def __init__(self, ann, span):
        self.ann = ann
        self.span = span

    def __enter__(self):
        self.ann.__enter__()
        self.span.__enter__()
        return self

    def __exit__(self, *exc):
        self.span.__exit__(*exc)
        return self.ann.__exit__(*exc)


def step_programs(cfg, opts, *, mesh=None, verify: bool = False):
    """The engine's prefill, decode and verify step programs, unjitted
    (verify is None unless ``verify``). ``mesh`` serves tensor-parallel
    through ``distribution/tp.py``'s shard_map wrappers.

    Greedy sampling runs inside each step so only token ids (plus one
    finite-logits bit per slot — the non-finite guard) cross the device
    boundary every iteration, never logits."""
    import jax.numpy as jnp

    from repro.models import lm
    if mesh is not None:
        from repro.distribution import tp as tp_lib
        step_prefill = tp_lib.make_tp_prefill_paged(cfg, mesh, opts=opts)
        step_decode = tp_lib.make_tp_decode_paged(cfg, mesh, opts=opts)
        step_verify = (tp_lib.make_tp_verify_paged(cfg, mesh, opts=opts)
                       if verify else None)
    else:
        def step_prefill(params, tokens, cache, tables, start):
            return lm.prefill_paged(params, cfg, tokens, cache, tables,
                                    start, opts)

        def step_decode(params, token, cache, tables, lens):
            return lm.decode_step_paged(params, cfg, token, cache, tables,
                                        lens, opts)

        def step_verify(params, tokens, cache, tables, lens):
            return lm.verify_step_paged(params, cfg, tokens, cache, tables,
                                        lens, opts)

    def _prefill(params, tokens, cache, tables, start):
        logits, cache = step_prefill(params, tokens, cache, tables, start)
        ok = jnp.isfinite(logits).all(-1)
        return jnp.argmax(logits, -1).astype(jnp.int32), ok, cache

    # ``scale`` is the fault harness's jit-compatible poison operand:
    # all-ones normally, NaN rows inject non-finite logits at chosen
    # steps without retracing.
    def _decode(params, token, cache, tables, lens, scale):
        logits, cache = step_decode(params, token, cache, tables, lens)
        logits = logits * scale
        ok = jnp.isfinite(logits).all(-1)
        return jnp.argmax(logits, -1).astype(jnp.int32), ok, cache

    # Verify: greedy argmax at each of the K draft positions; one
    # finite bit per slot covers all K (any non-finite position
    # invalidates the whole burst). ``scale`` is the same (B, 1)
    # poison operand, broadcast over K.
    def _verify(params, tokens, cache, tables, lens, scale):
        logits, cache = step_verify(params, tokens, cache, tables, lens)
        logits = logits * scale[:, :, None]
        ok = jnp.isfinite(logits).all(-1).all(-1)
        return jnp.argmax(logits, -1).astype(jnp.int32), ok, cache

    return _prefill, _decode, (_verify if verify else None)


class ServingEngine:
    """Binds a model to the scheduler and serves a request list.

    Decode runs on every step for all ready slots; at most one prefill
    chunk runs per step. Greedy (argmax) sampling keeps runs deterministic
    so the paged pipeline can be checked token-for-token against the dense
    reference path — and so a preempted-and-resumed request reproduces its
    uninterrupted output exactly.

    ``tp > 1`` serves tensor-parallel over a 1-D device mesh
    (distribution/tp.py): parameters are column/row-sharded, the page
    pools are kv-head-sharded, and the jitted steps run inside shard_map —
    so the autotuned ``paged_decode`` kernel launches (and tunes) on
    per-shard local shapes under mesh-signature cache keys. Greedy
    sampling stays deterministic: logits are replicated after the
    per-layer psums, so TP output is token-for-token the single-device
    output.

    Failure handling (docs/serving.md): both jitted steps return a
    per-slot finite-logits flag; a non-finite decode step fails that
    request, quarantines the active ``paged_decode`` config through the
    default tuner, and re-jits so the post-quarantine fallback config
    takes effect. An installed ``FaultPlan`` (serving/faults.py) can
    poison logits and hog pool pages at chosen steps.
    """

    def __init__(self, cfg, params, *, num_pages: int, page_size: int,
                 max_batch: int, max_seq_len: int, prefill_chunk: int = 8,
                 opts=None, quant=None, tp: int = 1,
                 prefix_cache: bool = False, record_cache_events: bool = False,
                 record_events: bool = False, speculative: int = 0,
                 tracer: Optional[trace_lib.Tracer] = None,
                 metrics: Optional[metrics_lib.MetricsRegistry] = None,
                 drift: Optional[drift_lib.DriftDetector] = None):
        import jax
        import jax.numpy as jnp

        from repro.models import lm
        from repro.quant import get_policy, quantize_params

        self.cfg = cfg
        # Draft-and-verify speculative decoding (docs/serving.md): with
        # speculative = K >= 2, decode steps score K positions per
        # sequence through the ``paged_verify`` kernel — one committed
        # token plus K-1 self-speculative n-gram drafts — and commit the
        # greedily-matched prefix. Greedy accept/rollback makes output
        # token-for-token identical to plain decode; only throughput
        # changes. K < 2 is plain one-token decode.
        self.spec_k = int(speculative) if int(speculative) >= 2 else 1
        self._spec_disabled = False    # degrade switch: verify faults
        self._drafters: Dict[int, Any] = {}     # rid -> NgramDrafter
        self.spec_steps = 0            # per-slot verify dispatches
        self.spec_committed = 0        # tokens committed by those
        self.spec_fallbacks = 0        # verify faults degraded to decode
        self.pool = PagePool(num_pages, page_size)
        # Cross-request prefix caching (docs/serving.md): retired (and
        # preempted) sequences park their pages in a radix tree instead of
        # freeing them, and admissions reuse any cached full-page prefix.
        # Works unchanged under kv8 int8 pools (scales ride the same
        # tables) and TP kv-head-sharded pools (the pool is host-side
        # bookkeeping shared by every shard).
        self.prefix_cache = (
            PrefixCache(self.pool, record_events=record_cache_events)
            if prefix_cache else None)
        self.tracer = tracer
        self.metrics = metrics
        self.drift = drift
        # Online drift-retune loop (ROADMAP item 5): when a detector flags
        # a dispatch key, the engine re-enqueues the scenario into the
        # background tuning queue (Autotuner.retune_key) and remembers the
        # key here; step() polls for the fresh cache entry and rebuilds
        # the jits once it lands, so subsequent dispatches trace with the
        # retuned config. Counters surface in the run report and the
        # metrics registry ("drift" provider).
        self._drift_hooked: set = set()
        self._drift_pending: Dict[str, float] = {}
        self._drift_stats = {"flagged": 0, "retunes": 0, "rejits": 0}
        self._drift_seen = False
        self.scheduler = Scheduler(
            self.pool, max_batch=max_batch,
            max_pages=self.pool.pages_for(max_seq_len),
            prefill_chunk=prefill_chunk, prefix_cache=self.prefix_cache,
            record_events=record_events, spec_k=self.spec_k,
            tracer=tracer, metrics=metrics)
        self.max_seq_len = int(max_seq_len)
        self.step_num = 0              # engine steps taken
        self._init_metrics()
        if opts is None:
            opts = lm.ForwardOpts(decode_impl="paged", quant=quant)
        elif quant is not None and opts.quant != quant:
            raise ValueError(
                f"quant={quant!r} conflicts with opts.quant={opts.quant!r}")
        self.opts = opts
        policy = get_policy(self.opts.quant)
        # Weight policies install QTensor leaves once at engine build; the
        # kv policy sizes int8 pools (+ per-token scale pools) instead.
        self.params = quantize_params(
            params, policy,
            store="grid" if self.opts.quant_impl == "sim" else "int8")
        kv_dtype = policy.kv_dtype if policy is not None else None
        self._jax = jax
        self._jnp = jnp
        self._annotation = jax.profiler.TraceAnnotation
        self._step_annotation = jax.profiler.StepTraceAnnotation

        self.tp = int(tp)
        self.mesh = None
        if self.tp > 1:
            from repro.distribution import tp as tp_lib
            if policy is not None and policy.quantizes_weights:
                raise NotImplementedError(
                    "tp > 1 with weight quantization needs QTensor-aware "
                    "param sharding; use tp=1 or the kv8 policy")
            self.mesh = tp_lib.make_tp_mesh(self.tp)
            self.params = tp_lib.shard_params(self.params, cfg, self.mesh)
            self.cache = tp_lib.init_cache(
                lm.paged_cache_specs(cfg, num_pages, page_size,
                                     kv_dtype=kv_dtype), self.mesh)
        else:
            self.cache = lm.init_paged_cache(cfg, num_pages, page_size,
                                             kv_dtype=kv_dtype)
        self._prefill_raw, self._decode_raw, self._verify_raw = \
            step_programs(cfg, self.opts, mesh=self.mesh,
                          verify=self.spec_k > 1)
        # Donate the cache on real accelerators: the previous pool buffers
        # are dead after every step, so donation avoids a full-pool copy
        # per token and 2x peak KV memory. On the CPU interpret-mode host
        # donation is unsupported (jax copies + warns and measurably slows
        # the step loop), so it is gated on the backend.
        self._donate = (2,) if jax.default_backend() != "cpu" else ()
        self._build_jits()
        # Block tables only change on admission / retirement / prefill
        # completion / page growth — cache their device copies keyed on
        # slot state so the steady decode loop does no host->device table
        # uploads.
        self._dev_tables_key = None
        self._dev_tables = None

    def _build_jits(self) -> None:
        jax = self._jax
        self._prefill_fn = jax.jit(self._prefill_raw,
                                   donate_argnums=self._donate)
        self._decode_fn = jax.jit(self._decode_raw,
                                  donate_argnums=self._donate)
        self._verify_fn = (jax.jit(self._verify_raw,
                                   donate_argnums=self._donate)
                           if self._verify_raw is not None else None)

    def _init_metrics(self) -> None:
        """Pre-create instruments and fold the existing stats surfaces
        (scheduler counters, tuner, prefix cache, speculation) into the
        registry as providers, so one snapshot covers the stack."""
        m = self.metrics
        if m is None:
            self._m_step: Dict[str, metrics_lib.Counter] = {}
            return
        self._m_ttft = m.histogram(
            "serving_ttft_ms", help="time to first token per request (ms)")
        self._m_itl = m.histogram(
            "serving_inter_token_ms",
            help="latency between consecutive tokens of a request (ms)")
        self._m_step = {
            f: m.counter(f"serving_{f}_total",
                         help=f"cumulative StepStats.{f} over all steps")
            for f in ("admitted", "retired", "prefill_tokens",
                      "decode_tokens", "prefix_cached_tokens", "preempted",
                      "failed", "timed_out", "degraded")}
        self._m_steps = m.counter("serving_steps_total",
                                  help="scheduler steps executed")
        sched = self.scheduler
        m.register_provider("scheduler", lambda: {
            "total_prefill_tokens": sched.total_prefill_tokens,
            "total_cached_tokens": sched.total_cached_tokens,
            "preemptions": sched.preemptions,
            "resumes": sched.resumes,
            "failures": sched.failures,
            "timeouts": sched.timeouts,
            "waiting": len(sched.waiting),
            "active_slots": sum(s is not None for s in sched.slots),
        })
        if self.prefix_cache is not None:
            m.register_provider("prefix_cache", self.prefix_cache.stats)
        if self.spec_k > 1:
            m.register_provider("speculative", lambda: {
                "draft_k": self.spec_k,
                "verify_steps": self.spec_steps,
                "committed_tokens": self.spec_committed,
                "accepted_per_step": (
                    self.spec_committed / max(1, self.spec_steps)),
                "fallbacks": self.spec_fallbacks,
            })

        def _tuner_stats():
            from repro.core.tuner import default_tuner
            return default_tuner().stats()

        m.register_provider("tuner", _tuner_stats)
        m.register_provider("drift", lambda: dict(self._drift_stats))

    def _span(self, name: str, step_num: Optional[int] = None, **args):
        """One host phase of a step as a ``jax.profiler`` annotation, on
        the profiler's clock when a profile is being taken (a
        ``StepTraceAnnotation`` when ``step_num`` is given). With a tracer
        attached the same span is also recorded on its ``scheduler``
        track."""
        if step_num is None:
            ann = self._annotation(name, **args)
        else:
            ann = self._step_annotation(name, step_num=step_num, **args)
            args = dict(step_num=step_num, **args)
        if self.tracer is None:
            return ann
        return _Both(ann, self.tracer.span(name, track="scheduler", **args))

    def _note_token(self, req: Request, t: float) -> None:
        """Record one generated-token timestamp (bounded) and feed the
        TTFT / inter-token histograms when a registry is attached."""
        prev = req.last_token_time
        req.note_token_time(t)
        if self.metrics is None:
            return
        if prev is None:
            if req.submitted_at is not None:
                self._m_ttft.observe((t - req.submitted_at) * 1e3)
        else:
            self._m_itl.observe((t - prev) * 1e3)

    def _drift_detector(self) -> Optional[drift_lib.DriftDetector]:
        return self.drift if self.drift is not None else drift_lib.get_active()

    def _observe_drift(self, det: drift_lib.DriftDetector, kernel: str,
                       seconds: float) -> None:
        """Feed one dispatch timing sample to ``det``, keyed by the tuner
        cache key of the kernel's last dispatch."""
        from repro.core.tuner import default_tuner
        tuner = default_tuner()
        item = tuner.last_dispatch(kernel)
        if item is None:
            return
        self._ensure_drift_hook(det)
        self._drift_seen = True
        key, shipped = tuner.dispatch_key(kernel, item[0])
        det.observe(key, seconds, shipped=shipped, kernel=kernel)

    def _ensure_drift_hook(self, det: drift_lib.DriftDetector) -> None:
        """Subscribe (once per detector) the retune-on-drift callback."""
        if id(det) in self._drift_hooked:
            return
        self._drift_hooked.add(id(det))
        det.on_drift(self._on_drift)

    def _on_drift(self, key: str, report: Dict[str, Any]) -> None:
        """A dispatch key regressed past the detector's threshold: count
        it and hand the scenario to the background tuning daemon. Fires
        synchronously from det.observe inside step()."""
        from repro.core.tuner import default_tuner
        self._drift_stats["flagged"] += 1
        if default_tuner().retune_key(key):
            self._drift_stats["retunes"] += 1
            self._drift_pending[key] = time.time()

    def _poll_drift_retunes(self) -> None:
        """Cheap per-step check (only while a retune is pending): once the
        background tune has written a fresh cache entry for a flagged key,
        rebuild the jits so the next trace re-resolves configs — the
        'subsequent dispatches use the new config' half of the loop — and
        reset the detector key so the new config calibrates its own
        baseline."""
        from repro.core.tuner import default_tuner
        tuner = default_tuner()
        done = []
        for key, flagged_at in self._drift_pending.items():
            item = tuner.lookup_key(key)
            if item is None:
                done.append(key)       # evicted: nothing left to wait for
                continue
            kernel, ctx = item
            entry = tuner.cache.get_raw(kernel.name, kernel.version,
                                        kernel.space, ctx)
            if entry is not None and entry.timestamp > flagged_at:
                done.append(key)
        if not done:
            return
        det = self._drift_detector()
        for key in done:
            del self._drift_pending[key]
            if det is not None:
                det.reset_key(key)
        self._drift_stats["rejits"] += 1
        self._build_jits()
        self._dev_tables_key = None
        self._dev_tables = None

    def _requarantine_and_rejit(self, kernel: str = "paged_decode") -> bool:
        """Non-finite step logits: quarantine the named kernel's config
        that traced into the current jit (if the dispatch is known) and
        rebuild the jitted steps so the next trace re-resolves configs
        post-quarantine."""
        from repro.core.tuner import default_tuner
        quarantined = default_tuner().quarantine_last(kernel)
        self._build_jits()
        self._dev_tables_key = None
        self._dev_tables = None
        return quarantined

    def _drafter(self, req: Request):
        """Per-request self-speculative drafter, fed the committed
        stream lazily (prompt + accepted tokens only — rejected drafts
        never enter, so the stream is append-only across rollbacks)."""
        from repro.serving.drafter import NgramDrafter
        d = self._drafters.get(req.rid)
        if d is None:
            d = self._drafters[req.rid] = NgramDrafter()
        stream = list(map(int, req.prompt)) + req.tokens
        d.observe(stream)
        return d

    def _check(self, req: Request) -> bool:
        if self.scheduler.max_tokens(req) > self.max_seq_len:
            self.scheduler.reject(
                req,
                f"prompt {req.prompt_len} + gen {req.max_new_tokens} "
                f"exceeds max_seq_len {self.max_seq_len}")
            return False
        return True

    def _dev_tables_for(self, mask: np.ndarray):
        """Device block tables for this step, cached keyed on (occupant,
        decode-ready, table length) per slot: a recycled slot (same
        mask, new request) or a slot that grew a page must re-upload
        its table row. Soundness rests on a slot's page list only ever
        growing while occupied (``commit_verify`` deliberately keeps
        the rejected-burst reservation for exactly this reason) — same
        rid at the same length always means the same page ids."""
        sched = self.scheduler
        key = tuple(
            (s.req.rid if s is not None else -1, bool(m),
             0 if s is None else len(s.pages))
            for s, m in zip(sched.slots, mask))
        if self._dev_tables is None or key != self._dev_tables_key:
            # Inactive rows (idle or mid-prefill) must write their
            # dummy token into the scratch page, not through their
            # real tables.
            tables = sched.block_tables()
            tables[~mask] = SCRATCH_PAGE
            self._dev_tables = self._jnp.asarray(tables)
            self._dev_tables_key = key
        return self._dev_tables

    def _step_inputs(self, mask: np.ndarray, toks: np.ndarray, plan):
        """Device inputs of a decode or verify step: tokens, block
        tables, lengths (0 for inactive slots) and the fault harness's
        per-slot logit scale."""
        jnp = self._jnp
        sched = self.scheduler
        lens = sched.lens() * mask
        scale = np.ones((sched.max_batch, 1), np.float32)
        if plan is not None:
            active = [int(b) for b in np.nonzero(mask)[0]]
            for s in plan.logit_poison(sched._step, active):
                scale[s] = float("nan")
        return (jnp.asarray(toks), self._dev_tables_for(mask),
                jnp.asarray(lens, jnp.int32), jnp.asarray(scale))

    def _step_verify(self, plan, stats: StepStats) -> None:
        """One speculative decode step for every ready slot: scatter the
        last committed token plus K-1 n-gram drafts, score all K
        positions in one ``paged_verify`` launch, and commit the
        greedily-accepted prefix (1..K tokens) with page rollback for
        the rejected tail.

        Output equals plain greedy decode token-for-token: position t's
        argmax is exactly what sequential decode would produce after
        x_0..x_t, and commits stop at the first draft that diverges.

        Fault degrade: a fault consumed by a ``paged_verify`` dispatch
        during trace (quarantine + ref fallback keep the traced step
        correct), or a non-finite verify burst at runtime, flips the
        engine to plain non-speculative decode. Non-finite bursts are
        *not* failed like decode steps — nothing is committed, the
        config is quarantined, and the same tokens are re-scored by
        plain decode next step, so the request still finishes
        token-identically."""
        sched = self.scheduler
        K = self.spec_k
        with self._span("serve.verify.prepare"):
            mask = sched.decode_mask(lookahead=K)
            if not mask.any():
                return
            toks = np.zeros((sched.max_batch, K), np.int32)
            for b in np.nonzero(mask)[0]:
                seq = sched.slots[int(b)]
                toks[b, 0] = seq.req.tokens[-1]
                toks[b, 1:] = self._drafter(seq.req).propose(K - 1)
            toks_d, tables, lens_d, scale_d = self._step_inputs(
                mask, toks, plan)
        log_n = len(plan.log) if plan is not None else 0
        det = self._drift_detector()
        with self._span("serve.verify.launch", slots=int(mask.sum())):
            t_disp = time.perf_counter()
            vtoks, vok, self.cache = self._verify_fn(
                self.params, toks_d, self.cache, tables, lens_d, scale_d)
            if plan is not None and any(
                    e.get("kernel") == "paged_verify"
                    for e in plan.log[log_n:]):
                # A verify dispatch consumed an injected fault while
                # tracing. The guarded dispatch already quarantined it
                # and traced a correct fallback, so this step's outputs
                # are still good — but the kernel is suspect: degrade to
                # plain decode.
                self._spec_disabled = True
                self.spec_fallbacks += 1
        with self._span("serve.verify.wait"):
            outs = np.asarray(vtoks)              # (B, K) greedy argmax
            okh = np.asarray(vok).reshape(-1)
            if plan is not None:
                slow = plan.take_slowdown("paged_verify")
                if slow > 0:
                    time.sleep(slow)   # inside the timing window: drift-visible
            t = time.perf_counter()
        with self._span("serve.verify.commit"):
            if det is not None:
                self._observe_drift(det, "paged_verify", t - t_disp)
            committed = 0
            for b in np.nonzero(mask)[0]:
                b = int(b)
                if not okh[b]:
                    continue
                seq = sched.slots[b]
                req = seq.req
                # Longest accepted prefix: position t's output is
                # committed while every draft before it matched the
                # model's choice.
                a = 0
                while a < K - 1 and toks[b, a + 1] == outs[b, a]:
                    a += 1
                take = min(a + 1, req.max_new_tokens - len(req.tokens))
                req.tokens.extend(int(x) for x in outs[b, :take])
                for _ in range(take):
                    self._note_token(req, t)
                sched.commit_verify(b, take)
                committed += take
                self.spec_steps += 1
            if not okh[mask].all():
                # Non-finite verify logits: commit nothing for those
                # slots, quarantine the verify config, and fall back to
                # plain decode — the request survives and re-scores next
                # step.
                self._spec_disabled = True
                self.spec_fallbacks += 1
                stats.degraded += 1
                self._requarantine_and_rejit("paged_verify")
            self.spec_committed += committed
            stats.decode_tokens = committed

    def _step_decode(self, plan, stats: StepStats) -> None:
        """One plain decode step for every ready slot: one token each
        through ``paged_decode``, a non-finite step failing its request
        and re-jitting past the quarantined config."""
        sched = self.scheduler
        with self._span("serve.decode.prepare"):
            mask = sched.decode_mask()
            if not mask.any():
                return
            toks = np.zeros((sched.max_batch, 1), np.int32)
            for b in np.nonzero(mask)[0]:
                toks[b, 0] = sched.slots[int(b)].req.tokens[-1]
            toks_d, tables, lens_d, scale_d = self._step_inputs(
                mask, toks, plan)
        det = self._drift_detector()
        with self._span("serve.decode.launch", slots=int(mask.sum())):
            t_disp = time.perf_counter()
            dtoks, dok, self.cache = self._decode_fn(
                self.params, toks_d, self.cache, tables, lens_d, scale_d)
        with self._span("serve.decode.wait"):
            next_tok = np.asarray(dtoks)
            okh = np.asarray(dok).reshape(-1)
            if plan is not None:
                slow = plan.take_slowdown("paged_decode")
                if slow > 0:
                    time.sleep(slow)   # drift-visible injected latency
            t = time.perf_counter()
        with self._span("serve.decode.commit"):
            if det is not None:
                # The asarray above synced the step, so t - t_disp is the
                # full dispatch-to-host latency of this launch.
                self._observe_drift(det, "paged_decode", t - t_disp)
            rejit = False
            for b in np.nonzero(mask)[0]:
                seq = sched.slots[int(b)]
                if okh[b]:
                    seq.req.tokens.append(int(next_tok[b]))
                    self._note_token(seq.req, t)
                else:
                    # Garbage argmax tokens must never reach the caller:
                    # fail the request and quarantine the decode config.
                    sched.fail_slot(int(b), "non-finite decode logits")
                    rejit = True
            if rejit:
                self._requarantine_and_rejit()
            sched.advance_decoded(mask & okh)
            stats.decode_tokens = int((mask & okh).sum())

    def _step_prefill(self, chunk, stats: StepStats) -> None:
        """Run this step's prompt chunk; the chunk that completes a fresh
        prompt reads its last position back as the first token."""
        jnp = self._jnp
        sched = self.scheduler
        b, tokens, start, valid = chunk
        seq = sched.slots[b]
        rid = int(seq.req.rid)
        with self._span("serve.prefill.launch", rid=rid, slot=int(b),
                        start=int(start), tokens=int(valid)):
            table = jnp.asarray(sched.block_tables()[b:b + 1])
            ptoks, pok, self.cache = self._prefill_fn(
                self.params, jnp.asarray(tokens[None]), self.cache,
                table, jnp.asarray([start], jnp.int32))
            sched.mark_prefilled(b, valid)
            stats.prefill_tokens = valid
        if not seq.prompt_done or seq.req.tokens:
            # Mid-prompt chunks are never read back. (A resumed sequence
            # skips the read too: its next token is the last generated
            # one, re-entering through decode.)
            return
        # First generated token comes straight from prefill argmax.
        with self._span("serve.prefill.wait", rid=rid):
            ok = bool(np.asarray(pok)[0, valid - 1])
            tok = int(ptoks[0, valid - 1]) if ok else None
        if ok:
            seq.req.tokens.append(tok)
            self._note_token(seq.req, time.perf_counter())
        else:
            sched.fail_slot(b, "non-finite prefill logits")

    def step(self, now: float = float("inf")) -> StepStats:
        """One scheduler iteration; returns what happened.

        Each host phase is a span (``_span``) inside one ``serve.step``
        span, in order: ``serve.retire``, ``serve.admit`` (with the
        choice of this step's prompt chunk), ``serve.prefill.launch``,
        ``serve.prefill.wait`` (only when the chunk completes a prompt),
        then ``serve.decode.{prepare,launch,wait,commit}`` or, when
        speculating, ``serve.verify.*``. ``serve.step`` carries
        ``perf_ns``, ``time.perf_counter_ns()`` at entry: the anchor that
        maps the program's clock (``Request`` stamps, tracer timestamps)
        onto the profiler's."""
        self.step_num += 1
        with self._span("serve.step", step_num=self.step_num,
                        perf_ns=time.perf_counter_ns()):
            return self._step(now)

    def _step(self, now: float) -> StepStats:
        sched = self.scheduler
        plan = fault_lib.get_active()
        if self._drift_pending:
            self._poll_drift_retunes()
        stats = StepStats()
        pre = (sched.preemptions, sched.failures, sched.timeouts)
        with self._span("serve.retire"):
            retired = sched.retire_finished()
            for req in retired:
                self._drafters.pop(req.rid, None)
        stats.retired = len(retired)
        with self._span("serve.admit"):
            admitted = sched.admit(now)
            stats.admitted = len(admitted)
            stats.prefix_cached_tokens = sum(
                sched.slots[b].cached_tokens for b in admitted)
            if plan is not None:
                plan.on_step(sched._step, self.pool)
            chunk = sched.next_prefill()
        if chunk is not None:
            self._step_prefill(chunk, stats)
        if self.spec_k > 1 and not self._spec_disabled:
            self._step_verify(plan, stats)
        else:
            self._step_decode(plan, stats)
        stats.preempted = sched.preemptions - pre[0]
        stats.failed = sched.failures - pre[1]
        stats.timed_out = sched.timeouts - pre[2]
        if self.metrics is not None:
            self._m_steps.inc()
            for f, c in self._m_step.items():
                c.inc(getattr(stats, f))
        return stats

    def run(self, requests: List[Request], *,
            real_time: bool = False) -> Dict[str, Any]:
        """Serve ``requests`` until every one reaches a terminal state.
        With ``real_time`` arrivals and deadlines are honored against the
        wall clock; otherwise every request is eligible immediately
        (arrival still orders admission, deadlines are not enforced)."""
        for req in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            if self._check(req):
                self.scheduler.submit(req)
        plan = fault_lib.get_active()
        t0 = time.perf_counter()
        steps = 0
        stalls = 0
        while self.scheduler.has_work():
            now = (time.perf_counter() - t0) if real_time else float("inf")
            stats = self.step(now)
            steps += 1
            if stats.progressed():
                stalls = 0
                continue
            if real_time and self.scheduler.waiting:
                time.sleep(1e-4)   # idle: wait for the next arrival
                continue
            if (self.scheduler.backoff_pending()
                    or (plan is not None and plan.pending())):
                # Preemption backoff / a fault hogging pages: the step
                # clock advances every iteration, so these resolve.
                if (not real_time
                        and not any(s is not None
                                    for s in self.scheduler.slots)
                        and (plan is None or not plan.pending())):
                    # Nothing is running and the only pending work is
                    # waiting out backoff: jump the virtual step clock
                    # to the earliest re-admission instead of burning
                    # one idle device-free step per backoff tick.
                    self.scheduler.fast_forward_backoff()
                stalls += 1
                if stalls > 100_000:
                    raise RuntimeError("scheduler made no progress "
                                       "(stalled in backoff)")
                continue
            raise RuntimeError("scheduler made no progress")
        self.scheduler.retire_finished()
        for req in requests:
            self._drafters.pop(req.rid, None)
        if plan is not None:
            plan.release_all(self.pool)
        wall = time.perf_counter() - t0
        # Report on THIS call's requests only — scheduler.finished
        # accumulates across runs on a reused engine.
        gen = sum(len(r.tokens) for r in requests)
        sched = self.scheduler
        out = {
            "requests": sum(r.done() for r in requests),
            "generated_tokens": gen,
            "steps": steps,
            "wall_s": wall,
            "tokens_per_s": gen / max(wall, 1e-9),
            "t0": t0,
            "preemptions": sched.preemptions,
            "resumes": sched.resumes,
            "failed_requests": sum(
                r.state is RequestState.FAILED for r in requests),
            "timed_out_requests": sum(
                r.state is RequestState.TIMED_OUT for r in requests),
            "terminal_requests": sum(r.terminal() for r in requests),
            "latency": latency_summary(requests),
        }
        if self.spec_k > 1:
            out["speculative"] = {
                "draft_k": self.spec_k,
                "verify_steps": self.spec_steps,
                "committed_tokens": self.spec_committed,
                "accepted_per_step": (
                    self.spec_committed / max(1, self.spec_steps)),
                "fallbacks": self.spec_fallbacks,
                "degraded": self._spec_disabled,
            }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self._drift_seen:
            drift_out = dict(self._drift_stats)
            drift_out["pending_retunes"] = len(self._drift_pending)
            det = self._drift_detector()
            if det is not None:
                rep = det.report()
                drift_out["tracked_keys"] = rep["tracked_keys"]
                drift_out["flagged_keys"] = rep["flagged_keys"]
            out["drift"] = drift_out
        return out
