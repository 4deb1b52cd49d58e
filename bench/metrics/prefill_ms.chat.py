"""Median wall time of ``prefill_slot`` inside the window (batch-1
prefill of one prompt; it ends in a host sync)."""
import numpy as np


def read(v):
    if not v.prefills:
        return None
    return float(np.median([p.t1 - p.t0 for p in v.prefills])) * 1e3
