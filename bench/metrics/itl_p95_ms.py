"""95th percentile of every gap between consecutive output tokens of every
request, both tokens inside the window (prefill stalls included)."""
import numpy as np


def read(v):
    gaps = [b - a for r in v.requests for a, b in zip(r.times, r.times[1:])
            if v.inside(a) and v.inside(b)]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
