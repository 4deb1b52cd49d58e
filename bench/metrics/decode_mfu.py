"""Tokens per second times the weight-contraction FLOPs of one token
(2 x matmul parameters, head included) over the chip's bf16 peak."""
from bench import shapes


def read(v):
    if not v.steps or v.peak is None:
        return None
    n = sum(1 for r in v.requests for t in r.times if v.inside(t))
    flops = n / v.window_s * 2 * shapes.matmul_params(v.config)
    return 100.0 * flops / v.peak["bf16_flops_per_s"]
