"""A test-only cell: the dense decoder at a size the CPU interprets in
seconds, driven through the same harness as the chip cells."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny-dense", "source": "test only", "reference": "dense_decoder",
    "n_layers": 2, "d_model": 128, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 32, "d_ff": 256, "vocab": 256,
    "mlp": "silu_gated", "rope_theta": 10000.0, "norm_eps": 1e-06,
    "tie_embeddings": True, "dtype": "bfloat16",
    "serving": {"backend": "pallas", "posit_width": 16, "variant": "L-21b",
                "control_posit_width": 8, "cache_dtype": "uint16",
                "page_size": 16},
}

MIX = {
    "clients": 4, "batch": 4, "max_len": 64,
    "prompt": {"dist": "uniform", "min": 16, "max": 32, "lengths": [16, 32]},
    "output": {"dist": "uniform", "min": 8, "max": 24},
    # every served request is compared, so a fault in one slot is always
    # in the sample
    "stagger": "budget", "strata": 8, "check_requests": 64,
}


def cell(trace=False, limit=None, mix=None):
    from bench import harness
    harness.check_data(CONFIG, mix or MIX)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    like = "yi-6b-l16.batch_decode"
    metrics = [m for m in spec[kind] if like in m.get("workloads", [like])]
    limits = None if limit is None else {"logit_gap": {"limit": limit}}
    return harness.Cell(name="tiny", chips=1, config=copy.deepcopy(CONFIG),
                        mix=copy.deepcopy(mix or MIX), limits=limits,
                        metrics=metrics)
