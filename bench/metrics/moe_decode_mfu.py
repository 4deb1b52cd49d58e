"""Tokens per second times the FLOPs a token does on this chip in an expert
model (2 x its attention and head weights, and its share of the routed
expert rows, ``bench/moe_shapes``) over the chip's bf16 peak."""
from bench import moe_shapes


def read(v):
    calls = moe_shapes.expert_calls(v)
    if not v.steps or v.peak is None or calls is None:
        return None
    n = sum(1 for r in v.requests for t in r.times if v.inside(t))
    dense = 2.0 * sum(k * n_ for k, n_ in
                      moe_shapes.attention_head_matmuls(v.config))
    flops = dense + moe_shapes.expert_flops_per_token(calls, v.config)
    return 100.0 * n / v.window_s * flops / v.peak["bf16_flops_per_s"]
