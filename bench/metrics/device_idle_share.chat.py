"""1 - (union of device op intervals) / (traced window), from the trace."""


def read(v):
    if v.trace is None or v.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - v.trace.busy_s() / v.trace.window_s)
