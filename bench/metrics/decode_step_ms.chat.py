"""Device milliseconds of one decode-step program call (``jit_decode_step``),
the mean over the calls in the trace."""
import numpy as np

import readers


def read(rec):
    calls = readers.device_calls(rec, "decode")
    return float(np.mean(calls)) * 1e3 if calls else None
