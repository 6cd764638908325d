"""setup_s (end to end, host clock): from the process's start to the
window's opening - weights, pool sizing, compiles and cache loads,
prefix prefill, warm-up and the ramp."""


def read(run):
    return run.setup_s
