"""Share of the window's wall time spent in ``prefill_slot`` (benchmark
span around the engine call, which ends in a host sync)."""


def read(v):
    if not v.steps:
        return None
    return 100.0 * sum(p.t1 - p.t0 for p in v.prefills) / v.window_s
