"""itl_p95_ms (ms): 95th percentile of every gap between consecutive output
tokens of a request, both tokens inside the window (host clock)."""

from harness.result import percentile


def read(run):
    return percentile(run.gaps_ms(), 95)
