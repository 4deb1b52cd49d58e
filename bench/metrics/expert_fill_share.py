"""Share of the expert layers' fixed capacity that carries routed rows, over
the window's prefills and decode steps: ``expert_rows / expert_slots``,
the program's own counters (``bench/moe_shapes``)."""
from bench import moe_shapes


def read(v):
    calls = moe_shapes.expert_calls(v)
    if calls is None:
        return None
    return 100.0 * sum(r for _, r, _ in calls) / sum(s for _, _, s in calls)
