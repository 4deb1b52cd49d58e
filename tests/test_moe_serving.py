"""Serving a sparse-expert model on a device that holds a share of the
experts (Mellum2-12B-A2.5B's layer at the ``SMOKE`` size).

The program is held to the plain f32 reference (``moe_reference``, given
the same share): prefill, then decode through the paged posit-word cache,
on the ``pallas`` backend (interpreted here) with the expert stacks held as
posit words and contracted by ``logmac_experts``.  The configuration has
a full-attention layer (YaRN) and window layers whose window is shorter
than the sequence.  Separately: the four shares of one expert layer add up
to the uncut layer, dispatch drops nothing and ignores a token's
neighbours, and the counters count what the layer did.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moe_reference as ref
from repro import numerics as N
from repro.configs import mellum2_12b_a2p5b as M
from repro.core.engine import EulerConfig, from_variant
from repro.kernels import logmac as LM
from repro.models import layers as L
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.numerics import stored
from repro.serving import (GenerationConfig, PagedKVConfig, RequestBatcher,
                           ServeEngine)

# this device holds experts 4-7 of 8; window 8 < the 26-token sequence;
# layer 3 of 4 is the full-attention (YaRN) layer
CFG = M.SMOKE.replace(router_experts=8, n_experts=4, expert_offset=4,
                      window=8, dtype="bfloat16")
B, TP, T, PS = 2, 16, 26, 8
# Posit-16 logits lie within 0.013 of the reference here (bf16 activations
# between the posit contractions, L-21b's approximate multiplier); Posit-8
# lies 0.43-0.56 away.  0.05 leaves ~4x room above the first and fails the
# second by ~9x.
LOGIT_TOL = 0.05


def _ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in ref.READS}


def _nctx(ecfg, backend="pallas"):
    return N.NumericsContext(policy=N.PrecisionPolicy.uniform(ecfg),
                             backend=backend)


@pytest.fixture(scope="module")
def weights():
    m = Model(CFG, EulerConfig(mode="exact"), remat=False)
    params = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, CFG.vocab)
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), ids


def _paged_logits(m, params, ctx, ids):
    """Logits of a batch-2 prefill of ``TP`` tokens, then of teacher-forced
    decode steps through a page pool holding the prefill's K/V."""
    cache_dtype = jnp.uint16 if ctx.numerics.backend == "pallas" else \
        jnp.float32
    logits, dense = jax.jit(lambda p, t, c: m.prefill(p, t, ctx, c))(
        params, ids[:, :TP], m.init_cache(B, TP, cache_dtype))
    out = [logits]
    n_logical = -(-T // PS)
    table = 2 + np.arange(B * n_logical, dtype=np.int32).reshape(B, -1)
    pool = {kk: jnp.zeros((CFG.n_layers, 2 + table.size, PS)
                          + dense[kk].shape[3:], dense[kk].dtype)
            for kk in ("k", "v")}
    for b in range(B):
        for j in range(TP // PS):
            for kk in ("k", "v"):
                pool[kk] = pool[kk].at[:, table[b, j]].set(
                    dense[kk][:, b, j * PS:(j + 1) * PS])
    decode = jax.jit(lambda p, t, pos, c: m.decode_step(
        p, t, pos, c, ctx, page_table=jnp.asarray(table)))
    for t in range(TP, T - 1):
        logits, pool = decode(params, ids[:, t], jnp.full((B,), t, jnp.int32),
                              pool)
        out.append(logits)
    return np.stack([np.asarray(x)[:, :CFG.vocab] for x in out], 1)


def _widest(got, ids, cfg, params):
    want = np.asarray(ref.logits(params, _ref_cfg(cfg), ids))[:, TP - 1:-1]
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("width", [16, 8])
def test_prefill_and_paged_decode_match_reference(weights, width):
    """Posit-16 serving stays within LOGIT_TOL of the reference at every
    position; Posit-8, the next precision down, does not.  Every weight
    contraction, the three expert stacks' included, reads held words."""
    params, ids = weights
    ecfg = from_variant(width, "L-21b")
    m = Model(CFG, ecfg, remat=False, numerics=_nctx(ecfg))
    served = m.hold_weights(params, ecfg.posit)
    wi = served["layers"]["moe"]["wi"]["w"]
    assert isinstance(wi, stored.PositWeight)
    assert wi.scale.shape == (CFG.n_layers, CFG.n_experts)
    assert not isinstance(served["layers"]["moe"]["router"]["w"],
                          stored.PositWeight)
    ctx = Ctx(ecfg=ecfg, numerics=_nctx(ecfg))
    with stored.tally() as reads:
        got = _paged_logits(m, served, ctx, ids)
    # prefill and decode: wq wk wv wo, the expert stacks wi wg wo, the head
    assert reads == {"stored": 2 * 8, "per_call": 0}
    gap = _widest(got, ids, CFG, params)
    if width == 16:
        assert gap < LOGIT_TOL
    else:
        assert gap > LOGIT_TOL


@pytest.mark.parametrize("change", ["none", "no_yarn", "window_16"])
def test_yarn_and_window_are_exercised(weights, change):
    """In f32 numerics the program equals the reference; a program that
    left YaRN off the full layer, or kept 16 positions in the window
    layers, would not: the configuration exercises both."""
    params, ids = weights
    cfg = {"none": CFG, "no_yarn": CFG.replace(yarn_factor=0.0),
           "window_16": CFG.replace(window=16)}[change]
    cfg = cfg.replace(dtype="float32")
    ecfg = EulerConfig(mode="exact")
    m = Model(cfg, ecfg, remat=False)
    got = _paged_logits(m, params, Ctx(ecfg=ecfg), ids)
    gap = _widest(got, ids, CFG, params)
    if change == "none":
        assert gap < 1e-5
    else:
        assert gap > 1e-2


def _layer_moe(params, share):
    """Layer 0's MoE weights, the held stacks cut to ``share`` (a slice)."""
    m = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     params["layers"]["moe"])
    for n in ("wi", "wg", "wo"):
        m[n] = {"w": m[n]["w"][share]}
    return m


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four devices holding two experts each: their partial outputs of one
    expert layer add up to the reference layer with all eight held."""
    full = CFG.replace(n_experts=8, router_experts=0, expert_offset=0,
                       dtype="float32")
    m = Model(full, EulerConfig(mode="exact"), remat=False)
    params = m.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, CFG.d_model))
    ctx = Ctx(ecfg=EulerConfig(mode="exact"))
    parts = []
    for i in range(4):
        cfg = full.replace(n_experts=2, router_experts=8, expert_offset=2 * i)
        y, _, counts = L.moe_apply(_layer_moe(params, slice(2 * i, 2 * i + 2)),
                                   x, ctx, cfg, drop_free=True)
        parts.append(y)
        assert int(counts["expert_slots"]) == 2 * 10
    want = ref.experts(x.reshape(10, -1), _layer_moe(params, slice(None)),
                       CFG.top_k, 0)
    np.testing.assert_allclose(np.asarray(sum(parts)).reshape(10, -1),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_drop_free_dispatch_ignores_neighbours():
    """With float numerics each token's output is the gate-weighted sum of
    its held top-k experts applied to that token alone, whichever tokens
    share its batch: even when every token picks the same experts."""
    cfg = CFG.replace(dtype="float32")
    m = Model(cfg, EulerConfig(mode="exact"), remat=False)
    params = m.init(jax.random.PRNGKey(4))
    moe = _layer_moe(params, slice(None))
    # every token the same direction (so the same experts), scaled apart
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 1, cfg.d_model))
    x = x * jnp.arange(1.0, 13.0)[None, :, None]
    ctx = Ctx(ecfg=EulerConfig(mode="exact"))
    y, _, counts = L.moe_apply(moe, x, ctx, cfg, drop_free=True)
    h = x.reshape(12, -1)
    probs = jax.nn.softmax(h @ moe["router"]["w"], -1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    rows = 0
    for t in range(12):
        alone, _, _ = L.moe_apply(moe, x[:, t:t + 1], ctx, cfg,
                                  drop_free=True)
        want = jnp.zeros(cfg.d_model)
        for g, e in zip(gates[t], ids[t]):
            e = int(e) - cfg.expert_offset
            if 0 <= e < cfg.n_experts:
                rows += 1
                u = jax.nn.silu(h[t] @ moe["wg"]["w"][e]) * (
                    h[t] @ moe["wi"]["w"][e])
                want = want + g * (u @ moe["wo"]["w"][e])
        np.testing.assert_allclose(np.asarray(y[0, t]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(alone[0, 0]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert int(counts["expert_rows"]) == rows
    assert int(counts["expert_slots"]) == cfg.n_experts * 12
    # training's capacity (1.25) drops some of these same-expert tokens
    y_cap, _, _ = L.moe_apply(moe, x, ctx, cfg)
    assert not np.allclose(np.asarray(y_cap), np.asarray(y), atol=1e-5)


def test_logmac_experts_is_logmac_per_expert():
    """The expert kernel is the dense kernel with a leading grid axis: bit
    for bit each expert's ``logmac``, uint16 words read as they are."""
    ecfg = from_variant(16, "L-21b")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 1 << 16, (3, 16, 256)), jnp.uint32)
    b = jnp.asarray(rng.integers(0, 1 << 16, (3, 256, 128)), jnp.uint16)
    got = LM.logmac_experts(a, b, ecfg, bm=16, bn=128, bk=128)
    for e in range(3):
        np.testing.assert_array_equal(
            np.asarray(got[e]),
            np.asarray(LM.logmac(a[e], b[e], ecfg, bm=16, bn=128, bk=128)))


def test_serving_counts_experts(weights):
    """``RequestBatcher.stats`` and ``expert_log`` count every prefill and
    decode step: slots are held experts x rows x layers, rows the pairs the
    router sent to the held experts."""
    params, _ = weights
    ecfg = EulerConfig(mode="exact")
    nctx = _nctx(ecfg, "exact")
    m = Model(CFG, ecfg, remat=False, numerics=nctx)
    eng = ServeEngine(m, params, Ctx(ecfg=ecfg, numerics=nctx), max_len=32,
                      batch=2, numerics=nctx,
                      paged=PagedKVConfig(page_size=PS))
    b = RequestBatcher(eng)
    rng = np.random.default_rng(0)
    for n in (5, 12, 9):
        b.submit(rng.integers(0, CFG.vocab, n), max_new=4)
    b.run(GenerationConfig(max_new_tokens=4))
    per_row = CFG.n_experts * CFG.n_layers
    kinds = [k for _, k, _, _ in b.expert_log]
    assert kinds.count("prefill") == b.stats["prefills"] == 3
    assert kinds.count("decode") == b.stats["steps"]
    assert b.stats["expert_slots"] == per_row * (
        b.stats["prefill_tokens"] + eng.batch * b.stats["steps"])
    assert b.stats["expert_slots"] == sum(s for *_, s in b.expert_log)
    assert b.stats["expert_rows"] == sum(r for _, _, r, _ in b.expert_log)
    # a token-layer sends at most one row to each held expert
    assert 0 < b.stats["expert_rows"] <= b.stats["expert_slots"]


@pytest.mark.parametrize("offset,held", [(-1, 4), (5, 4), (0, 9)])
def test_held_experts_must_be_routed(offset, held):
    with pytest.raises(ValueError, match="not among the router's 8"):
        CFG.replace(expert_offset=offset, n_experts=held)


def test_dense_model_logs_no_expert_calls():
    """A model without experts counts nothing and logs nothing."""
    cfg = dataclasses.replace(CFG, family="dense", d_ff=64, n_experts=0,
                              router_experts=0, expert_offset=0)
    ecfg = EulerConfig(mode="exact")
    nctx = _nctx(ecfg, "exact")
    m = Model(cfg, ecfg, remat=False, numerics=nctx)
    eng = ServeEngine(m, m.init(jax.random.PRNGKey(0)),
                      Ctx(ecfg=ecfg, numerics=nctx), max_len=32, batch=2,
                      numerics=nctx, paged=PagedKVConfig(page_size=PS))
    b = RequestBatcher(eng)
    b.submit(np.arange(6), max_new=3)
    b.run(GenerationConfig(max_new_tokens=3))
    assert b.stats["expert_rows"] == b.stats["expert_slots"] == 0
    assert b.expert_log == []
