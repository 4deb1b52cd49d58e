"""Median wall time of ``step_slots`` inside the window (one decode step
of every slot; it ends in a host sync)."""
import numpy as np


def read(v):
    if not v.steps:
        return None
    return float(np.median([s.t1 - s.t0 for s in v.steps])) * 1e3
