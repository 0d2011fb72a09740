"""decode_step_ms_p95: 95th percentile over all steps of the window of the
gap between tokens: input put on the device, dispatch, tokens on the host."""
import numpy as np


def read(run):
    if run.kind != "decode":
        return None
    return float(np.percentile(run.step_s, 95)) * 1e3
