"""The measured window: an open loop driving ``ServingEngine.step``.

Requests are handed to the scheduler when they come due, by this loop
and no server. Each request is timed from its due time, so a stalled
step delays the requests behind it. Requests due inside the window
count; after the window the loop runs on only until each of them
has its first token (or has failed), so the time to first token is
complete. Tokens and gaps count inside the window only.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

DRAIN_LIMIT_S = 150.0        # after the window, at most this long


@dataclasses.dataclass
class Record:
    """One request as the harness saw it (host clock, seconds)."""
    req: object
    due: float                   # absolute time it came due
    submitted: float             # when this loop handed it over
    running: Optional[float] = None     # first step that found it RUNNING


@dataclasses.dataclass
class Step:
    """One engine step as the harness saw it."""
    t0: float
    t1: float
    prefill_tokens: int
    prefill_start: int           # resident tokens before the chunk
    decode_ctx: List[int]        # tokens each decoded token attends to
    cached_tokens: int           # prompt tokens admitted from the cache
    admitted_prompt_tokens: int  # prompt tokens of the requests admitted
    waiting: int                 # requests in the scheduler's queue after


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                    # the window's close
    records: List[Record]        # requests due in the window
    residents: List[Record]      # requests resident when it opened
    steps: List[Step]
    compiles: List[str]          # programs compiled inside the window
    trace: Optional[dict] = None


class _Annotate:
    """A profiler span around the harness's own work, when tracing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        if self.on:
            return self._ann(name)
        return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


def _snapshot(sched):
    return [None if s is None else (id(s), s.pos, s.prompt_done)
            for s in sched.slots]


def _step_record(sched, before, stats, t0, t1) -> Step:
    """What one step did, read from the slots before and after it."""
    decode_ctx, start, admitted_prompt = [], 0, 0
    for b, seq in enumerate(sched.slots):
        if seq is None:
            continue
        prev = before[b]
        if prev is None or prev[0] != id(seq):       # admitted this step
            admitted_prompt += len(seq.view)
            pos0, done0 = seq.cached_tokens, False
        else:
            pos0, done0 = prev[1], prev[2]
        grown = seq.pos - pos0
        if done0:
            if grown >= 1:
                decode_ctx.append(seq.pos)
        elif grown > 0:
            start = pos0
            if grown == stats.prefill_tokens + 1:    # prefilled, then decoded
                decode_ctx.append(seq.pos)
    return Step(t0=t0, t1=t1, prefill_tokens=stats.prefill_tokens,
                prefill_start=start, decode_ctx=decode_ctx,
                cached_tokens=stats.prefix_cached_tokens,
                admitted_prompt_tokens=admitted_prompt,
                waiting=len(sched.waiting))


class CompileCounter:
    """Programs the backend compiles while armed, with their seconds."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, armed: bool = False):
        import jax
        self.names: List[str] = []
        self.seconds = 0.0
        self.armed = armed
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += duration


def warm_start(engine, arrivals, clock=time.perf_counter) -> List[Record]:
    """Submit the resident requests (``traffic.warm_start``) and step the
    engine until each has its prompt prefilled: set-up, not window."""
    from repro.serving import Request
    sched = engine.scheduler
    out = []
    for i, a in enumerate(arrivals):
        req = Request(rid=i, prompt=a.prompt,
                      max_new_tokens=a.max_new_tokens)
        now = clock()
        out.append(Record(req=req, due=now, submitted=now))
        sched.submit(req)
    while any(not r.req.tokens and not r.req.terminal() for r in out):
        engine.step()
    return out


def run(engine, arrivals, seconds: float, *, residents=(),
        trace: Optional[Dict] = None, clock=time.perf_counter) -> Window:
    """Drive ``engine`` with ``arrivals`` (``traffic.generate``, in the
    order they come due) for a window of ``seconds``. ``trace``, when
    given, is ``{"start": s, "seconds": d, "dir": path}``: the profiler
    records from ``s`` seconds into the window for ``d`` seconds."""
    from repro.serving import Request, RequestState
    sched = engine.scheduler
    annotate = _Annotate(trace is not None)
    compiles = CompileCounter()
    records: List[Record] = []
    steps: List[Step] = []
    by_rid: Dict[int, Record] = {r.req.rid: r for r in residents}
    next_rid = max(by_rid, default=-1) + 1

    t0 = clock()
    t1 = t0 + seconds
    pending = [a for a in arrivals if a.due < seconds]
    nxt = 0                     # the next of ``pending`` to submit
    trace_on = trace_off = None
    if trace is not None:
        trace_on = t0 + trace["start"]
        trace_off = trace_on + trace["seconds"]
    traced = None
    compiles.armed = True
    finished_seen = len(sched.finished)
    waiting_first = set()       # rids due in the window with no token yet

    while True:
        now = clock()
        if trace_on is not None and now >= trace_on and traced is None:
            import jax
            jax.profiler.start_trace(trace["dir"])
            traced = {"start": clock()}
        if traced is not None and "stop" not in traced and now >= trace_off:
            import jax
            traced["stop_called"] = clock()
            jax.profiler.stop_trace()
            traced["stop"] = clock()
        with annotate("bench.submit"):
            while nxt < len(pending) and t0 + pending[nxt].due <= now:
                a = pending[nxt]
                nxt += 1
                req = Request(rid=next_rid, prompt=a.prompt,
                              max_new_tokens=a.max_new_tokens)
                next_rid += 1
                rec = Record(req=req, due=t0 + a.due, submitted=clock())
                records.append(rec)
                by_rid[req.rid] = rec
                waiting_first.add(req.rid)
                sched.submit(req)
        if now >= t1:
            waiting_first = {r for r in waiting_first
                             if not by_rid[r].req.token_times
                             and not by_rid[r].req.terminal()}
            if not waiting_first and (traced is None or "stop" in traced):
                break
            if now - t1 > DRAIN_LIMIT_S:
                break
        if sched.has_work():
            before = _snapshot(sched)
            s0 = clock()
            with annotate("bench.step"):
                stats = engine.step()
            s1 = clock()
            steps.append(_step_record(sched, before, stats, s0, s1))
            for seq in sched.slots:
                if seq is not None and by_rid[seq.req.rid].running is None:
                    by_rid[seq.req.rid].running = s0
            # A request admitted and finished within one step.
            for req in sched.finished[finished_seen:]:
                rec = by_rid[req.rid]
                if rec.running is None and \
                        req.state is RequestState.FINISHED:
                    rec.running = s0
            finished_seen = len(sched.finished)
        else:
            wake = [t0 + pending[nxt].due] if nxt < len(pending) else []
            if traced is None and trace_on is not None:
                wake.append(trace_on)
            elif traced is not None and "stop" not in traced:
                wake.append(trace_off)
            if now < t1:
                wake.append(t1)
            with annotate("bench.wait"):
                time.sleep(min(max(0.0, min(wake, default=now) - clock()),
                               0.05))
    compiles.armed = False
    return Window(t0=t0, t1=t1, records=records,
                  residents=list(residents), steps=steps,
                  compiles=compiles.names, trace=traced)
