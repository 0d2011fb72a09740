"""mlp_ms.decode: device time per traced decode step of the operations under
the program's ``mlp`` scope (bench/scopes.py), in ms, mean over the chips.
None without a trace, where no operation of the step carries a scope, or
where this one holds none."""
from bench.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "decode", "mlp")
