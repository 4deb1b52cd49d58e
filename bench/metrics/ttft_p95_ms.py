"""95th percentile over requests submitted inside the window of first
token minus submit (a request with no first token yet counts its wait to
the window's end)."""
import numpy as np


def read(v):
    waits = [(r.first if r.first is not None else v.w1) - r.submit
             for r in v.requests if v.inside(r.submit)]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
