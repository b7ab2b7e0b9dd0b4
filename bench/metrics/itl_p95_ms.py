"""95th percentile of every gap between consecutive tokens of a request,
as they leave ``Server.step()``, over all requests in the window."""
import numpy as np


def read(rec):
    gaps = [b - a for r in rec["requests"] for a, b in zip(r["tokens"], r["tokens"][1:])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
