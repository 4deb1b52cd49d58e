"""Median time from submit to the start of the request's prefill, over
requests submitted inside the window: the wait for earlier admissions."""
import numpy as np


def read(v):
    waits = [r.prefill_start - r.submit for r in v.requests
             if v.inside(r.submit) and r.prefill_start is not None]
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
