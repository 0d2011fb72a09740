"""mfu.decode: the traced steps' roofline share. The least time the chips
could take, the larger of the model FLOPs over peak FLOP/s and the necessary
HBM bytes (weights read, cache read once and written) over peak bandwidth
(bench/counts.py), over the traced window, in percent. Decode is bound by
the bytes."""


def read(run):
    if run.kind != "decode" or run.trace is None or run.peak is None:
        return None
    flops, nbytes = run.traced_work
    least = max(flops / run.peak.flops, nbytes / run.peak.hbm_bw) / run.chips
    return 100.0 * least / run.trace.window_s
