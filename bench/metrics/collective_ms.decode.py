"""collective_ms.decode: collective time per traced decode step, the union of
the device's collective operations whether compute hides them or not
(bench/trace.py), in ms, mean over the chips. Beside
``collective_exposed_ms.decode`` it says how much of the exchange the
overlap hides. A one-chip cell has no collectives to read."""


def read(run):
    if run.kind != "decode" or run.trace is None or run.chips == 1:
        return None
    return 1e3 * run.trace.collective_s / run.traced_steps
