"""collective_exposed_ms.decode: collective time per traced decode step
during which the same device runs no other operation (bench/trace.py), in
ms, mean over the chips. A one-chip cell has no collectives to read."""


def read(run):
    if run.kind != "decode" or run.trace is None or run.chips == 1:
        return None
    return 1e3 * run.trace.collective_exposed_s / run.traced_steps
