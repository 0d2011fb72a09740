"""idle_share.decode: the share of the traced window in which no operation runs
on the device (1 - busy / window, busy the union of the device's operation
intervals, bench/trace.py), in percent, mean over the chips."""


def read(run):
    if run.kind != "decode" or run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
