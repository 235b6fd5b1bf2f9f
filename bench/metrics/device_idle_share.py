"""device_idle_share (%), device: 1 - (union of device operation intervals
over the traced window), from the profiler trace."""


def read(run):
    red = run.reduce()
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
