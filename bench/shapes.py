"""Required work of the serving step, from a configuration's sizes.

Counted by what the algorithm needs, not by how the program does it
today: a weight is read once per call at the policy's posit width, an
activation is encoded once, and padding, re-encodes and plane decodes are
not work.
"""
from __future__ import annotations

GATED = ("silu_gated", "gelu_gated")


def weight_matmuls(config: dict) -> list[tuple[int, int]]:
    """(K, N) of every weight contraction one token passes through; the
    output head is the last."""
    d, H, KV, hd = (config["d_model"], config["n_heads"],
                    config["n_kv_heads"], config["head_dim"])
    f = config["d_ff"]
    per_layer = [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
                 (d, f), (f, d)]
    if config["mlp"] in GATED:
        per_layer.append((d, f))
    vocab_padded = -(-config["vocab"] // 16) * 16
    return per_layer * config["n_layers"] + [(d, vocab_padded)]


def matmul_params(config: dict) -> int:
    return sum(k * n for k, n in weight_matmuls(config))


def contractions(v):
    """(M, K, N) of every weight contraction in the window: a decode step
    runs all ``batch`` slots through every one; a prefill runs its prompt
    length through the layers and its last position through the head."""
    mm = weight_matmuls(v.config)
    for _ in v.steps:
        for K, N in mm:
            yield v.batch, K, N
    for p in v.prefills:
        for K, N in mm[:-1]:
            yield p.length, K, N
        yield 1, *mm[-1]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def posit_bytes(config: dict) -> float:
    return config["serving"]["posit_width"] / 8


def cache_word_bytes(config: dict) -> int:
    return {"uint8": 1, "uint16": 2, "uint32": 4}[
        config["serving"]["cache_dtype"]]
