"""attention_ms.prefill: device time per traced prefill step of the operations
under the program's ``attn_core`` scope: the chunked causal attention over
the prompt (bench/scopes.py), in ms, mean over the chips. None without a
trace, where no operation of the step carries a scope, or where this one
holds none."""
from bench.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "prefill", "attn_core")
