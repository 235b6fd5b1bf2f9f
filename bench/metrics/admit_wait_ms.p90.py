"""admit_wait_ms.p90 (ms), scheduler: 90th percentile of the scheduler's own
wait, ``Request.submitted_at`` (stamped in ``Scheduler.submit``) to
``Request.admitted_at`` (first admission to a slot), over the requests due
in the window that were admitted. A program without those stamps gives
nothing to read."""

from harness.result import percentile


def read(run):
    waits = []
    for r in run.window.records:
        sub = getattr(r.req, "submitted_at", None)
        adm = getattr(r.req, "admitted_at", None)
        if sub is not None and adm is not None:
            waits.append((adm - sub - run.paused(sub, adm)) * 1e3)
    return percentile(waits, 90)
