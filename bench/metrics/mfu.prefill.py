"""mfu.prefill: the model FLOPs a causal prefill needs (bench/counts.py) for
the traced steps, over the traced window, the chips and their bf16 peak, in
percent."""


def read(run):
    if run.kind != "prefill" or run.trace is None or run.peak is None:
        return None
    flops, _ = run.traced_work
    return 100.0 * flops / (run.trace.window_s * run.chips * run.peak.flops)
