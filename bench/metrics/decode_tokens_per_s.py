"""decode_tokens_per_s: tokens served in the window over its length."""


def read(run):
    return run.items / run.window_s if run.kind == "decode" else None
