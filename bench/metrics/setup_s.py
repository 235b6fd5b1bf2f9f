"""setup_s (s): process start to the window's opening: imports, weights
drawn on the device, engine and pool built, both step programs compiled or
loaded from the compile cache and run once."""


def read(run):
    return run.setup_s
