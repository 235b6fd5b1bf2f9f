"""queue_wait_ms.p90 (ms), scheduler: 90th percentile of due time to the
first step that found the request RUNNING (admitted to a slot), over the
requests due in the window that were admitted."""

from harness.result import percentile


def read(run):
    waits = [(r.running - r.due - run.paused(r.due, r.running)) * 1e3
             for r in run.window.records if r.running is not None]
    return percentile(waits, 90)
