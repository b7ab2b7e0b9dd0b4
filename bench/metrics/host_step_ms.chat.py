"""Host milliseconds of one ``Server.step()`` less the time it waits on the
device (its ``harvest.wait`` spans), the mean over the program's
``server.step`` spans in the trace; None where the run's record holds none."""
import numpy as np


def read(rec):
    steps = rec.get("trace", {}).get("host_steps")
    return float(np.mean([s - w for s, w in steps])) * 1e3 if steps else None
