"""prefill_span_ms.p90 (ms), scheduler: 90th percentile of
``Request.admitted_at`` to ``Request.prefilled_at`` (the step whose chunk
completed the prompt), over the requests due in the window whose prompt
completed. A program without those stamps gives nothing to read."""

from harness.result import percentile


def read(run):
    spans = []
    for r in run.window.records:
        adm = getattr(r.req, "admitted_at", None)
        done = getattr(r.req, "prefilled_at", None)
        if adm is not None and done is not None:
            spans.append((done - adm - run.paused(adm, done)) * 1e3)
    return percentile(spans, 90)
