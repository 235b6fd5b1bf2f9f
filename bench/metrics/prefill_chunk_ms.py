"""prefill_chunk_ms (ms), model step: device time per call of the prefill
program (the engine's jitted ``_prefill``), from the profiler trace."""

PROGRAM = r"jit__prefill"


def read(run):
    p = run.reduce(programs={"prefill": PROGRAM}).get("programs")
    if not p or not p["prefill"]["calls"]:
        return None
    return 1e3 * p["prefill"]["seconds"] / p["prefill"]["calls"]
