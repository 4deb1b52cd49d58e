"""Process start to window start: weights, compiles, warm-up, slot fill."""


def read(v):
    return v.setup_s
