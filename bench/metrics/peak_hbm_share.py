"""peak_hbm_share (%), device: ``peak_bytes_in_use`` of the fullest chip
after the window, over the HBM of its ``device_kind`` (harness/peaks.py)."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return 100.0 * run.memory_peak_bytes / run.peaks["hbm_bytes"]
