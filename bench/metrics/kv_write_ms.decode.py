"""kv_write_ms.decode: device time per traced decode step of the operations
under the program's ``kv_write`` scope: the new token's keys and values
inserted into each layer's cache (bench/scopes.py), in ms, mean over the
chips. None without a trace, where no operation of the step carries a scope,
or where this one holds none."""
from bench.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "decode", "kv_write")
