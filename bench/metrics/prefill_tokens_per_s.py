"""prefill_tokens_per_s: prompt tokens prefilled in the window over its
length."""


def read(run):
    return run.items / run.window_s if run.kind == "prefill" else None
