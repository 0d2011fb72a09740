"""setup_s: seconds from the start of the process to the first timed step
(start-up, compile or compile-cache load, weights and inputs, warm-up)."""


def read(run):
    return run.setup_s
