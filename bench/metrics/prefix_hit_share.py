"""prefix_hit_share (%), page pool / prefix cache: prompt tokens served from
cached pages at admission (StepStats.prefix_cached_tokens) over the prompt
tokens of the requests admitted, inside the window."""


def read(run):
    steps = run.steps_in_window()
    total = sum(s.admitted_prompt_tokens for s in steps)
    if not total:
        return None
    return 100.0 * sum(s.cached_tokens for s in steps) / total
