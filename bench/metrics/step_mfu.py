"""step_mfu (%), model step: the model's operations for the prompt and
decoded tokens of the window's steps (the configuration's counts,
harness/flops.py, from the widths and each token's resident context) over
the steps' host-clocked time times the chip's bf16 peak."""

from harness import flops


def read(run):
    steps = run.steps_in_window()
    if not steps:
        return None
    counts = flops.counts_for(run.config, run.bench_dir)
    work = sum(counts.prefill_flops(run.config, s.prefill_start,
                                    s.prefill_tokens)
               + counts.decode_flops(run.config, s.decode_ctx)
               for s in steps)
    busy = sum(s.t1 - s.t0 for s in steps)
    if work <= 0 or busy <= 0:
        return None
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
