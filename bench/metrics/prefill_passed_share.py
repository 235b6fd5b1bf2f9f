"""prefill_passed_share (%), scheduler: of the engine steps in which a
request had prompt tokens left (``Request.prefill_steps``, from admission
through its last chunk), the share in which its chunk waited while another
slot's chunk ran (``prefill_steps - prefill_chunks``), over the requests due
in the window whose prompt completed. A program without those counters
gives nothing to read."""


def read(run):
    steps = passed = 0
    for r in run.window.records:
        if getattr(r.req, "prefilled_at", None) is None:
            continue
        steps += r.req.prefill_steps
        passed += r.req.prefill_steps - r.req.prefill_chunks
    return 100.0 * passed / steps if steps else None
