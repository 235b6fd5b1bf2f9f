"""What a run measured, as the metric readers see it.

Each reader in ``bench/metrics/<name>.py`` is ``read(run) -> float | None``
over a ``Run``; None means it found nothing to read, and the metric is
left out of the result line.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

from harness import trace as trace_lib


@dataclasses.dataclass
class Run:
    config: Dict
    window: object                      # window.Window
    peaks: Dict[str, float]
    memory_peak_bytes: int
    events: Optional[Dict] = None       # trace.capture(), traced runs only
    setup_s: Optional[float] = None
    bench_dir: Optional[str] = None     # where the cell's files are

    # -- requests and tokens (host clock) ---------------------------------
    def paused(self, due: float, t: float) -> float:
        """Seconds between ``due`` and ``t`` in which the loop stood still
        while the profiler wrote its trace (traced runs only): measurement,
        not serving, so a wait that spans it is read without it."""
        tr = self.window.trace
        if not tr or "stop" not in tr or not due < tr["stop_called"] <= t:
            return 0.0
        return tr["stop"] - tr["stop_called"]

    def ttft_ms(self) -> List[float]:
        """Due time to first token of every request due in the window; a
        request with no first token counts as missing (infinite)."""
        out = []
        for r in self.window.records:
            ts = r.req.token_times
            out.append((ts[0] - r.due - self.paused(r.due, ts[0])) * 1e3
                       if ts else math.inf)
        return out

    def served(self):
        """Every request that made tokens in the window: those due in it
        and those resident when it opened."""
        return self.window.records + self.window.residents

    def gaps_ms(self) -> List[float]:
        """Gaps between consecutive tokens of a request, both inside the
        window."""
        t0, t1 = self.window.t0, self.window.t1
        out = []
        for r in self.served():
            ts = [t for t in r.req.token_times if t0 <= t <= t1]
            out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        return out

    def tokens_in_window(self) -> int:
        t0, t1 = self.window.t0, self.window.t1
        return sum(t0 <= t <= t1 for r in self.served()
                   for t in r.req.token_times)

    def steps_in_window(self):
        t0, t1 = self.window.t0, self.window.t1
        return [s for s in self.window.steps if s.t0 >= t0 and s.t1 <= t1]

    # -- device trace ----------------------------------------------------
    def reduce(self, programs: Dict[str, str] = None,
               kernels: Dict[str, str] = None) -> Dict:
        if not self.events:
            return {}
        return trace_lib.reduce(self.events, programs=programs or {},
                                kernels=kernels or {})

    def traced_steps(self):
        """Step records paired with their ``bench.step`` spans in the
        trace, in order: [(Step, [start_ns, dur_ns])]."""
        tr = self.window.trace
        if not self.events or not tr:
            return []
        steps = [s for s in self.window.steps
                 if s.t0 >= tr["start"] and s.t1 <= tr["stop_called"]]
        spans = [[s, d] for n, s, d in self.events["host"]
                 if n == "bench.step"]
        if len(spans) != len(steps):
            # The profiler may drop a partial span at either end; pair
            # from the end of the shorter list only when one is missing.
            k = min(len(spans), len(steps))
            if abs(len(spans) - len(steps)) > 2 or k == 0:
                return []
            steps, spans = steps[-k:], spans[-k:]
        return list(zip(steps, spans))


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear rule); None when empty or
    infinite."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, dtype=float), q))
    return v if math.isfinite(v) else None
