"""Serving weights held as posit words (``numerics.stored``).

The engine encodes each weight a contraction reads whole once, when it
takes its weights; the ``pallas`` backend then feeds ``logmac`` the stored
words.  These tests hold the stored path to the per-call path bit for bit
on the same float tree (tied head, scanned layer stack), and check that
every other backend, wrapper and ladder level still contracts the float
leaf.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import numerics as N
from repro.core import engine as E
from repro.core.engine import from_variant
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.numerics import stored
from repro.numerics.backends import PallasBackend
from repro.serving import (GenerationConfig, PagedKVConfig, RequestBatcher,
                           ServeEngine)

P8, P16 = from_variant(8, "L-21b"), from_variant(16, "L-21b")
# compute in bf16 over f32 leaves, so the head's cast is part of the path
CFG = ModelConfig(name="held", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  mlp="silu_gated", dtype="bfloat16", loss_chunk=32,
                  q_chunk=32, kv_chunk=32)
# wq wk wv wo, wi wg wo (one scanned site each) and the head
SITES = 8
GEN = GenerationConfig(max_new_tokens=4)


class PerCallPallas(PallasBackend):
    """The pallas kernels with held weights handed over as floats."""
    reads_words = False


N.register_backend("pallas_per_call", PerCallPallas())


def _nctx(ecfg, backend="pallas"):
    return N.NumericsContext(policy=N.PrecisionPolicy.uniform(ecfg),
                             backend=backend)


@pytest.fixture(scope="module")
def model_params():
    m = Model(CFG, P16, remat=False, numerics=_nctx(P16))
    return m, m.init(jax.random.PRNGKey(0))


def _served(m, params, ecfg):
    return m.hold_weights(params, ecfg.posit)


def _prefill_decode(m, params, ctx, steps=1):
    """Logits of a batch-2 prefill and of ``steps`` greedy decode steps."""
    prefill = jax.jit(lambda p, t, c: m.prefill(p, t, ctx, c))
    decode = jax.jit(lambda p, t, pos, c: m.decode_step(p, t, pos, c, ctx))
    toks = jnp.asarray(np.random.default_rng(1).integers(1, CFG.vocab,
                                                         (2, 8)), jnp.int32)
    logits, cache = prefill(params, toks, m.init_cache(2, 16, jnp.uint16))
    out = [logits]
    pos = jnp.full((2,), 8, jnp.int32)
    for _ in range(steps):
        tok = jnp.argmax(out[-1], -1).astype(jnp.int32)
        logits, cache = decode(params, tok, pos, cache)
        out.append(logits)
        pos = pos + 1
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("width", [16, 8])
def test_words_at_format_width_with_per_slice_scale(model_params, width):
    m, params = model_params
    ecfg = from_variant(width, "L-21b")
    pc = ecfg.posit
    served = _served(m, params, ecfg)
    wi = served["layers"]["mlp"]["wi"]["w"]
    assert isinstance(wi, stored.PositWeight)
    assert wi.words.dtype == {16: jnp.uint16, 8: jnp.uint8}[width]
    assert wi.words.shape == wi.w.shape and wi.scale.shape == (2,)
    for layer in range(2):
        f = params["layers"]["mlp"]["wi"]["w"][layer].astype(jnp.float32)
        s = E._pow2_scale(f)
        assert float(wi.scale[layer]) == float(s)
        want = ops.encode(f / s, pc).astype(pc.storage_dtype)
        np.testing.assert_array_equal(wi.words[layer], want)
    head = served["head"]
    emb = params["embed"]["e"].astype(jnp.bfloat16).astype(jnp.float32)
    assert head.words.shape == (CFG.d_model, CFG.vocab_padded)
    s = E._pow2_scale(emb.T)
    np.testing.assert_array_equal(
        head.words, ops.encode(emb.T / s, pc).astype(pc.storage_dtype))
    # the float leaves are the caller's own arrays
    assert served["embed"]["e"] is params["embed"]["e"]
    assert wi.w is params["layers"]["mlp"]["wi"]["w"]
    assert stored.held(served)[0].pc == pc
    assert len(stored.held(served)) == SITES


@pytest.mark.parametrize("width", [16, 8])
def test_stored_words_bit_identical_to_per_call(model_params, width):
    """Prefill and decode logits (and so the greedy tokens) from the
    stored words equal the per-call path's on the same float tree, and
    every weight contraction read words."""
    m, params = model_params
    ecfg = from_variant(width, "L-21b")
    ctx = Ctx(ecfg=ecfg, numerics=_nctx(ecfg))
    with stored.tally() as reads:
        got = _prefill_decode(m, _served(m, params, ecfg), ctx)
    want = _prefill_decode(m, params, ctx)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert reads == {"stored": 2 * SITES, "per_call": 0}


@pytest.mark.parametrize("backend,steps", [
    ("faulty:pallas", 1), ("guarded:pallas", 0), ("lax_ref", 1)])
def test_other_backends_contract_the_float_leaf(model_params, backend,
                                                steps):
    m, params = model_params
    ctx = Ctx(ecfg=P16, numerics=_nctx(P16, backend))
    served = _served(m, params, P16)
    with stored.tally() as reads:
        got = _prefill_decode(m, served, ctx, steps)
    want = _prefill_decode(m, params, ctx, steps)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert reads == {"stored": 0, "per_call": (1 + steps) * SITES}


def test_words_at_another_width_take_the_per_call_path(model_params):
    """P16 words under a P8 context (a ladder level below the primary):
    the float leaf, per call, as without words."""
    m, params = model_params
    ctx = Ctx(ecfg=P8, numerics=_nctx(P8))
    with stored.tally() as reads:
        got = _prefill_decode(m, _served(m, params, P16), ctx)
    want = _prefill_decode(m, params, ctx)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert reads == {"stored": 0, "per_call": 2 * SITES}


def _engine(m, params, backend="pallas", levels=None):
    return ServeEngine(m, params, Ctx(ecfg=P16, numerics=_nctx(P16, backend)),
                       max_len=32, batch=2, cache_dtype=jnp.uint16,
                       levels=levels, paged=PagedKVConfig(page_size=8))


def _drain(eng, lengths=(9, 20, 5)):
    b = RequestBatcher(eng)
    rng = np.random.default_rng(0)
    for n in lengths:
        b.submit(rng.integers(1, CFG.vocab, n).astype(np.int32),
                 max_new=GEN.max_new_tokens)
    return b.run(GEN, key=jax.random.PRNGKey(1)), b


def test_engine_drain_reads_words_and_matches_per_call(model_params):
    m, params = model_params
    eng = _engine(m, params)
    assert eng.params is params
    assert eng.word_leaves == SITES
    assert eng.word_bytes == sum(
        int(w.words.nbytes) for w in stored.held(eng.served))
    res, b = _drain(eng)
    assert b.stats["refills"] >= 1
    assert eng.weight_reads == {("serve_prefill", 0): (SITES, 0),
                                ("serve_decode", 0): (SITES, 0)}
    assert (b.stats["weight_leaves"], b.stats["weight_bytes"]) == (
        SITES, eng.word_bytes)
    assert (b.stats["stored_reads"], b.stats["per_call_reads"]) == (
        2 * SITES, 0)
    ref_eng = _engine(m, params, backend="pallas_per_call")
    assert ref_eng.word_leaves == 0 and ref_eng.served is params
    ref, _ = _drain(ref_eng)
    assert res.keys() == ref.keys()
    for rid in res:
        np.testing.assert_array_equal(res[rid], ref[rid])


def test_ladder_level_at_another_width_encodes_per_call(model_params):
    m, params = model_params
    eng = _engine(m, params, levels=[_nctx(P16), _nctx(P8)])
    toks = np.arange(1, 9, dtype=np.int32)
    eng.prefill_slot(0, toks, GEN, jax.random.PRNGKey(0), level=1)
    eng.prefill_slot(1, toks, GEN, jax.random.PRNGKey(0), level=0)
    assert eng.weight_reads == {("serve_prefill", 1): (0, SITES),
                                ("serve_prefill", 0): (SITES, 0)}


@pytest.mark.parametrize("backend", ["faulty:pallas", "guarded:pallas",
                                     "lax_ref"])
def test_engine_holds_no_words_for_other_backends(model_params, backend):
    m, params = model_params
    eng = _engine(m, params, backend=backend)
    assert eng.word_leaves == 0 and eng.served is params


def test_reassigning_params_reencodes(model_params):
    m, params = model_params
    eng = _engine(m, params)
    old = stored.held(eng.served)
    params2 = jax.tree.map(lambda a: a * 2.0, params)
    eng.params = params2
    assert eng.params is params2
    assert eng.served["head"].w is params2["embed"]["e"]
    # doubling every weight moves each scale up one power of 2
    for got, was in zip(stored.held(eng.served), old):
        np.testing.assert_array_equal(got.scale, 2 * was.scale)
        np.testing.assert_array_equal(got.words, was.words)
    want = _served(m, params2, P16)
    for got, w in zip(stored.held(eng.served), stored.held(want)):
        np.testing.assert_array_equal(got.words, w.words)
        np.testing.assert_array_equal(got.scale, w.scale)
    eng.params = None
    assert eng.served is None and eng.word_leaves == eng.word_bytes == 0


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint16],
                         ids=lambda d: jnp.dtype(d).name)
def test_logmac_reads_words_at_their_width(rng, dtype):
    """A narrow weight operand goes into the kernel as is, and gives the
    same quire as its uint32 copy."""
    cfg = P8 if dtype == jnp.uint8 else P16
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    a = ops.encode(x, cfg.posit)
    b32 = ops.encode(w, cfg.posit)
    got = ops.logmac_matmul(a, b32.astype(dtype), cfg, bm=8, bn=16, bk=32)
    want = ops.logmac_matmul(a, b32, cfg, bm=8, bn=16, bk=32)
    np.testing.assert_array_equal(got, want)


def test_engine_counts_logmac_grid():
    """``stats`` counts logmac's grid steps and 128x128 sub-tile products
    over the engine's programs: at widths above 128 (d_ff 384, a 256-id
    head) a grid step spans several sub-tiles."""
    cfg = ModelConfig(name="wide", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=384, vocab=256,
                      mlp="silu_gated", dtype="bfloat16", loss_chunk=32,
                      q_chunk=32, kv_chunk=32)
    m = Model(cfg, P16, remat=False, numerics=_nctx(P16))
    eng = _engine(m, m.init(jax.random.PRNGKey(0)))
    _, b = _drain(eng, lengths=(9, 5))
    steps, tiles = b.stats["logmac_steps"], b.stats["logmac_tiles"]
    assert set(eng.logmac_grid) == {("serve_prefill", 0), ("serve_decode", 0)}
    assert (steps, tiles) == tuple(map(sum, zip(*eng.logmac_grid.values())))
    # per program: wq wk wv wo 1 step, 1 sub-tile each; wi wg 1 step of
    # 3 column sub-tiles, wo 1 step of 3 deep ones; the head 1 of 2
    for grid in eng.logmac_grid.values():
        assert grid == (8, 4 + 3 + 3 + 3 + 2)
    assert tiles > steps > 0
