"""Assigned-architecture configs: exact numbers + per-arch smoke tests
(reduced config, one forward/train step on CPU, shapes + no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.engine import from_variant
from repro.models.layers import Ctx
from repro.models.transformer import Model

ARCH_IDS = list(C.ALIASES)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    mod = C.get_config(arch)
    cfg, exp = mod.FULL, mod.EXPECTED
    for k, v in exp.items():
        got = getattr(cfg, k)
        assert got == v, f"{arch}.{k}: {got} != {v}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, key):
    """Reduced same-family config: one loss+grad step, finite, right shapes."""
    mod = C.get_config(arch)
    cfg = mod.SMOKE
    assert cfg.family == mod.FULL.family
    m = Model(cfg, from_variant(16, "L-21b"))
    params = m.init(key)
    ctx = Ctx(ecfg=m.ecfg)
    B, T = 2, 64
    ids = jax.random.randint(key, (B, T), 0, cfg.vocab)
    inputs = ids
    if cfg.embedding_inputs:
        inputs = jax.random.normal(key, (B, T, cfg.d_model)) * 0.1
    batch = {"inputs": inputs, "labels": ids}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: m.loss(p, batch, ctx)[0]))(params)
    assert jnp.isfinite(loss), arch
    for leaf in jax.tree.leaves(grads):
        assert jnp.isfinite(leaf).all(), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes(arch, key):
    mod = C.get_config(arch)
    cfg = mod.SMOKE
    m = Model(cfg, from_variant(16, "L-21b"))
    params = m.init(key)
    ctx = Ctx(ecfg=m.ecfg)
    B, T = 2, 32
    ids = jax.random.randint(key, (B, T), 0, cfg.vocab)
    inputs = ids
    if cfg.embedding_inputs:
        inputs = jax.random.normal(key, (B, T, cfg.d_model)) * 0.1
    h, _, _ = m.forward(params, inputs, ctx)
    assert h.shape == (B, T, cfg.d_model)
    logits = m.head(params, h, ctx)
    assert logits.shape == (B, T, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode(arch, key):
    mod = C.get_config(arch)
    cfg = mod.SMOKE
    m = Model(cfg, from_variant(16, "L-21b"))
    params = m.init(key)
    ctx = Ctx(ecfg=m.ecfg)
    B = 2
    cache = m.init_cache(B, 16)
    tok = jax.random.randint(key, (B,), 0, cfg.vocab)
    logits, cache2 = m.decode_step(params, tok, jnp.int32(3), cache, ctx)
    assert logits.shape == (B, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all())
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_shape_table():
    assert C.SHAPES["train_4k"] == {"seq_len": 4096, "global_batch": 256,
                                    "kind": "train"}
    assert C.SHAPES["long_500k"]["seq_len"] == 524_288
    cells = list(C.all_cells())
    assert len(cells) == 44
    applicable = [c for c in cells if c[2]]
    # 11 archs x 3 non-long shapes + long_500k for ssm & hybrid = 35
    assert len(applicable) == 35


def test_long500k_applicability():
    assert C.shape_applicable("mamba2-1.3b", "long_500k")
    assert C.shape_applicable("hymba-1.5b", "long_500k")
    for arch in ("yi-6b", "gemma2-27b", "arctic-480b", "chameleon-34b"):
        assert not C.shape_applicable(arch, "long_500k")


def test_tp_divisibility():
    """Every arch must TP-shard over 16: flattened projection dims and the
    padded vocab divide the model axis."""
    for arch in ARCH_IDS:
        cfg = C.get_config(arch).FULL
        assert cfg.vocab_padded % 16 == 0, arch
        if cfg.n_heads:
            assert (cfg.n_heads * cfg.head_dim) % 16 == 0, arch
            assert (cfg.n_kv_heads * cfg.head_dim) % 16 == 0, arch
        if cfg.d_ff:
            assert cfg.d_ff % 16 == 0, arch
        if cfg.family in ("ssm", "hybrid"):
            assert cfg.d_inner % 16 == 0, arch


# Mellum2-12B-A2.5B-Instruct's published config.json, as a benchmark
# configuration states it beside the program's keys (its untied head aside:
# the program always ties it)
_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
         "original_max_position_embeddings": 8192, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": 1.2772588722239782}
MELLUM_PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2304,
    intermediate_size=7168,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 7,
    mlp_layer_types=["sparse"] * 28, max_position_embeddings=131072,
    max_window_layers=0, model_type="mellum", moe_intermediate_size=896,
    norm_topk_prob=True, num_attention_heads=32, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=28, num_key_value_heads=4,
    rms_norm_eps=1e-06,
    rope_parameters={"full_attention": _YARN,
                     "sliding_attention": {"rope_type": "default",
                                           "rope_theta": 500000}},
    sliding_window=1024, vocab_size=98304, use_sliding_window=True)


def _mellum(**published):
    import dataclasses
    from repro.models.config import ModelConfig
    full = C.get_config("mellum2-12b-a2.5b").FULL
    own = {f.name: getattr(full, f.name) for f in dataclasses.fields(full)}
    return full, ModelConfig(**own, **{**MELLUM_PUBLISHED, **published})


def test_published_keys_are_accepted_where_they_match():
    """The published keys are checked, not stored: the config built with
    them equals the one built without; a depth cut keeps the first
    ``n_layers`` of ``layer_types``."""
    import inspect
    from repro.models.config import PUBLISHED, ModelConfig
    full, stated = _mellum()
    assert stated == full and hash(stated) == hash(full)
    assert (list(inspect.signature(ModelConfig).parameters)[-len(PUBLISHED):]
            == list(PUBLISHED))
    cut = full.replace(n_layers=16, n_experts=16, router_experts=64,
                       **{**MELLUM_PUBLISHED, "num_hidden_layers": 16,
                          "num_experts": 16})
    assert (cut.n_layers, cut.n_experts, cut.n_routed) == (16, 16, 64)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048),
    ("num_experts_per_tok", 4),
    ("attention_bias", True),
    ("hidden_act", "gelu"),
    ("norm_topk_prob", False),
    ("use_sliding_window", False),
    ("layer_types", ["full_attention"] + ["sliding_attention"] * 27),
    ("mlp_layer_types", ["dense"] * 28),
    ("rope_parameters", {"full_attention": {**_YARN, "factor": 8},
                         "sliding_attention": {"rope_type": "default",
                                               "rope_theta": 500000}}),
    ("rope_parameters", {"rope_type": "default", "rope_theta": 500000}),
])
def test_published_key_that_disagrees_is_refused(key, value):
    with pytest.raises(ValueError, match=f"{key}"):
        _mellum(**{key: value})
