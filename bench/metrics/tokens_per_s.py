"""Output tokens emitted inside the window over the window's seconds."""


def read(v):
    n = sum(1 for r in v.requests for t in r.times if v.inside(t))
    return n / v.window_s
