"""Mean over the window's decode steps of mapped pages over pool pages
(``PagedKVCache`` counters read before each step)."""
import numpy as np


def read(v):
    if not v.steps:
        return None
    return 100.0 * float(np.mean([s.live_pages / s.pool_pages for s in v.steps]))
