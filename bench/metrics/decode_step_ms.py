"""decode_step_ms (ms), model step: device time per call of the decode
program (the engine's jitted ``_decode``), from the profiler trace."""

PROGRAM = r"jit__decode"


def read(run):
    p = run.reduce(programs={"decode": PROGRAM}).get("programs")
    if not p or not p["decode"]["calls"]:
        return None
    return 1e3 * p["decode"]["seconds"] / p["decode"]["calls"]
