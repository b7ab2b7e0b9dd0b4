"""The whole decode step's share of the chip's roofline: the step's required
operations at peak or its required bytes at full bandwidth, whichever is
longer (bytes, at these sizes), over the step's device time; in percent."""
import numpy as np

import readers
import work


def read(rec):
    calls = readers.device_calls(rec, "decode")
    need = readers.mean_required(
        rec, "decode", lambda s: work.roofline_s(s["flops"], s["bytes"], rec["peak"]))
    if not calls or need is None:
        return None
    return 100.0 * need / float(np.mean(calls))
