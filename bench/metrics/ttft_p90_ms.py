"""ttft_p90_ms (ms): 90th percentile, over every request due in the window,
of its due time to its first token (host clock after the token was read
back). A request that failed or never got a token counts as missing."""

from harness.result import percentile


def read(run):
    return percentile(run.ttft_ms(), 90)
