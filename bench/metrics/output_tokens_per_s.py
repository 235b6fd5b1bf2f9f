"""output_tokens_per_s (tokens/s): output tokens generated inside the
window, over the window's seconds (host clock)."""


def read(run):
    w = run.window
    return run.tokens_in_window() / (w.t1 - w.t0)
