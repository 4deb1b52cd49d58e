"""A test-only cell: the sparse-expert decoder at a size the CPU interprets
in seconds (two window layers and a full YaRN layer, 4 of 8 experts held),
driven through the same harness as the chip cells."""
import copy
import json

from bench.tests.tiny import ROOT

CONFIG = {
    "name": "tiny-moe", "source": "test only", "reference": "moe_decoder",
    "family": "moe", "n_layers": 3, "d_model": 128, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 32, "d_ff": 64, "vocab": 256,
    "mlp": "silu_gated", "n_experts": 4, "router_experts": 8,
    "expert_offset": 4, "top_k": 2, "window": 16, "local_global_period": 3,
    "rope_theta": 500000.0, "yarn_factor": 16.0,
    "yarn_original_max_pos": 64, "yarn_beta_fast": 32.0,
    "yarn_beta_slow": 1.0, "yarn_attention_factor": 1.2772588722239782,
    "norm_eps": 1e-06, "tie_embeddings": True, "dtype": "bfloat16",
    "serving": {"backend": "pallas", "posit_width": 16, "variant": "L-21b",
                "control_posit_width": 8, "cache_dtype": "uint16",
                "page_size": 16},
}

MIX = {
    "clients": 4, "batch": 4, "max_len": 64,
    "prompt": {"dist": "uniform", "min": 16, "max": 32, "lengths": [16, 32]},
    "output": {"dist": "uniform", "min": 8, "max": 24},
    "stagger": "budget", "strata": 8, "check_requests": 64,
}

CELL = "mellum2-l16-e16.batch_decode_128"


def cell(trace=False, limit=None):
    from bench import harness
    harness.check_data(CONFIG, MIX)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind] if CELL in m.get("workloads", [CELL])]
    limits = None if limit is None else {"logit_gap": {"limit": limit}}
    return harness.Cell(name="tiny-moe", chips=1,
                        config=copy.deepcopy(CONFIG), mix=copy.deepcopy(MIX),
                        limits=limits, metrics=metrics)
