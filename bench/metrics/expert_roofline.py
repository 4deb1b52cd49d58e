"""``logmac_experts`` kernel time in the trace against the least time the
window's expert layers need: per call, the held experts' weights read once
at the policy's posit width, the routed rows' activations encoded once and
written in f32, 2 x 3 x d x f FLOPs per routed row (``bench/moe_shapes``)."""
from bench import moe_shapes, shapes

KERNEL = r"logmac_experts$"


def read(v):
    if v.trace is None or v.peak is None:
        return None
    t = v.trace.kernel_s(KERNEL)
    calls = moe_shapes.expert_calls(v)
    if t <= 0 or calls is None:
        return None
    need = sum(shapes.roofline_s(*moe_shapes.expert_work(rows, v.config),
                                 v.peak) for _, rows, _ in calls)
    return 100.0 * need / t
