"""Required work of a sparse-expert model's serving step, and the expert
counts the program keeps, for the expert model's per-layer metrics.

The expert layer's work follows the routing: ``expert_rows``, the
token-expert pairs the router sent to the experts this chip holds, which
the program counts on the device and logs per call
(``RequestBatcher.expert_log``: the host's ``perf_counter`` after the
call's sync, ``"prefill"`` or ``"decode"``, ``expert_rows``,
``expert_slots``).  Work is counted as the algorithm needs it: each held
expert's weights read once per call at the posit width, each routed row's
activations encoded once and its outputs written in f32, 2 x 3 x d x f
FLOPs per routed row; the rows the fixed capacity adds are not work.
"""
from __future__ import annotations

import gc
import sys

from bench import shapes


def expert_calls(v) -> list[tuple[str, int, int]] | None:
    """``(kind, expert_rows, expert_slots)`` of every prefill and decode
    step inside the window, from the live scheduler's log; None where the
    program keeps no such log or logged nothing in the window."""
    engine = sys.modules.get("repro.serving.engine")
    cls = getattr(engine, "RequestBatcher", None)
    if cls is None:
        return None
    calls = [(kind, rows, slots)
             for b in gc.get_objects() if isinstance(b, cls)
             for t, kind, rows, slots in getattr(b, "expert_log", ())
             if v.w0 <= t <= v.w1]
    return calls or None


def attention_head_matmuls(config: dict) -> list[tuple[int, int]]:
    """(K, N) of every weight contraction ``logmac`` runs for one token:
    the attention projections of each layer, then the output head."""
    d, H, KV, hd = (config["d_model"], config["n_heads"],
                    config["n_kv_heads"], config["head_dim"])
    per_layer = [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d)]
    vocab_padded = -(-config["vocab"] // 16) * 16
    return per_layer * config["n_layers"] + [(d, vocab_padded)]


def attention_head_contractions(v):
    """(M, K, N) of every ``logmac`` contraction in the window: a decode
    step runs all ``batch`` slots through each; a prefill runs its prompt
    through the layers and its last position through the head."""
    mm = attention_head_matmuls(v.config)
    for _ in v.steps:
        for K, N in mm:
            yield v.batch, K, N
    for p in v.prefills:
        for K, N in mm[:-1]:
            yield p.length, K, N
        yield 1, *mm[-1]


def expert_work(rows: int, config: dict) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the expert layers needs, summed over the
    layers, for ``rows`` routed token-expert pairs."""
    d, f = config["d_model"], config["d_ff"]
    word = shapes.posit_bytes(config)
    weights = config["n_layers"] * config["n_experts"] * 3 * d * f * word
    acts = rows * ((2 * d + f) * word + (2 * f + d) * 4.0)
    return 2.0 * rows * 3 * d * f, weights + acts


def expert_flops_per_token(calls, config: dict) -> float:
    """Expert FLOPs a token does on this chip, over the layers: its share
    of the routed rows (``expert_slots`` is held experts x tokens x
    layers) times 2 x 3 x d x f."""
    rows = sum(r for _, r, _ in calls)
    token_layers = sum(s for _, _, s in calls) / config["n_experts"]
    per_token = rows / token_layers * config["n_layers"]
    return per_token * 2.0 * 3 * config["d_model"] * config["d_ff"]
