"""layer_carry_ms.decode: device time per traced decode step of the operations
under the program's ``layers`` scope (the layer scan) and under no scope
inside it: the scan's slicing of the stacked cache into each layer and its
stacking of each layer's cache back (bench/scopes.py), in ms, mean over the
chips. None without a trace, where no operation of the step carries a scope,
or where this one holds none."""
from bench.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "decode", "layers")
