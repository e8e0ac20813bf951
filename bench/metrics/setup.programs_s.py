"""Election and compile: seconds of the warm-up phase, the autotune cache
loaded (or measured on a checkout's first run) and every bucket program the
mix can open compiled or loaded and run once."""


def read(run):
    return run.setup.get("programs_s")
