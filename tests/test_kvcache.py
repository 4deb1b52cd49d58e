"""Paged posit KV cache: allocator properties, paged-vs-dense decode
bit-parity (kernel, model and scheduler level), OOM backpressure /
preemption, and failover snapshot roundtrip with page tables."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EulerConfig
from repro.kernels.paged_decode import (NULL_PAGE, RESERVED_PAGES,
                                        TRASH_PAGE, gather_pages,
                                        paged_attention_reference,
                                        paged_flash_decode)
from repro.core import posit as P
from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.numerics import NumericsContext, PrecisionPolicy
from repro.serving import (DurableBatcher, GenerationConfig, PageAllocator,
                           PagedKVCache, PagedKVConfig, PagePoolOOM,
                           RequestBatcher, ServeEngine)

CFG = ModelConfig(name="kvc", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  loss_chunk=32, q_chunk=32, kv_chunk=32)


@pytest.fixture(scope="module")
def model_params():
    m = Model(CFG, EulerConfig(mode="exact"), remat=False)
    params = m.init(jax.random.PRNGKey(0))
    return m, params, Ctx(ecfg=m.ecfg)


def _euler_ctx(backend, width=16):
    ec = EulerConfig(width=width, mode="euler", stages=2)
    nctx = NumericsContext(policy=PrecisionPolicy.uniform(ec),
                           backend=backend)
    return Ctx(ecfg=ec, numerics=nctx), nctx


# ---------------------------------------------------------------------------
# allocator properties
# ---------------------------------------------------------------------------

def test_allocator_never_hands_out_reserved_pages():
    a = PageAllocator(10)
    pages = [a.alloc() for _ in range(a.free_count)]
    assert min(pages) == RESERVED_PAGES
    assert NULL_PAGE not in pages and TRASH_PAGE not in pages
    assert sorted(pages) == list(range(RESERVED_PAGES, 10))


def test_allocator_alloc_free_reuse_and_oom():
    a = PageAllocator(6)  # 4 usable
    p = [a.alloc() for _ in range(4)]
    with pytest.raises(PagePoolOOM):
        a.alloc()
    a.free(p[1])
    assert a.alloc() == p[1]  # LIFO reuse
    with pytest.raises(ValueError):
        a.free(p[2] + 100)  # out of range
    a.free(p[2])
    with pytest.raises(ValueError):
        a.free(p[2])  # double free


def test_allocator_fragmentation_churn_invariants():
    """Random alloc/free churn: no page is ever live twice, the free+used
    partition is exact, and the pool never leaks."""
    rng = np.random.default_rng(0)
    a = PageAllocator(34)
    live: list[int] = []
    for _ in range(500):
        if live and (rng.random() < 0.5 or a.free_count == 0):
            p = live.pop(int(rng.integers(len(live))))
            a.free(p)
        else:
            p = a.alloc()
            assert p not in live
            live.append(p)
        assert a.used_count == len(live)
        assert a.free_count + a.used_count == 32
    for p in live:
        a.free(p)
    assert a.free_count == 32


def test_paged_cache_alloc_grow_free_table():
    kv = PagedKVCache(batch=2, max_len=64, page_size=8, num_pages=12)
    pgs = kv.alloc_slot(0, 2)
    assert kv.n_pages(0) == 2 and list(kv.table[0, :2]) == pgs
    assert (kv.table[0, 2:] == NULL_PAGE).all()
    g = kv.grow_slot(0)
    assert kv.table[0, 2] == g and kv.n_pages(0) == 3
    kv.free_slot(0)
    assert kv.n_pages(0) == 0 and (kv.table[0] == NULL_PAGE).all()
    assert kv.alloc.used_count == 0


def test_paged_cache_admission_headroom_and_oom_state_unchanged():
    kv = PagedKVCache(batch=2, max_len=64, page_size=8, num_pages=11)
    # 9 usable pages; a 9-page request needs 9 + 1 headroom (not full-len)
    # n_logical = 8, so a full-length request takes all 8 with no headroom
    kv.alloc_slot(0, 8)
    free_before = kv.alloc.free_count
    with pytest.raises(PagePoolOOM):
        kv.alloc_slot(1, 1)  # 1 free page left: 1 + 1 headroom > 1
    assert kv.alloc.free_count == free_before  # state unchanged
    assert kv.n_pages(1) == 0


def test_paged_cache_snapshot_roundtrip():
    kv = PagedKVCache(batch=2, max_len=64, page_size=8, num_pages=12)
    kv.alloc_slot(0, 3)
    kv.alloc_slot(1, 2)
    kv.grow_slot(1)
    snap = kv.snapshot()
    kv2 = PagedKVCache(batch=2, max_len=64, page_size=8, num_pages=12)
    kv2.load(snap)
    np.testing.assert_array_equal(kv.table, kv2.table)
    assert kv2.alloc.used_count == kv.alloc.used_count
    # freshly restored allocator keeps handing out non-conflicting pages
    newp = kv2.grow_slot(0)
    assert newp not in set(kv.table.ravel())


# ---------------------------------------------------------------------------
# kernel level: gather semantics + fused flash-decode vs reference
# ---------------------------------------------------------------------------

def test_gather_pages_null_entries_read_zeros():
    pages = jnp.arange(5 * 4 * 2 * 3, dtype=jnp.float32).reshape(5, 4, 2, 3)
    pages = pages.at[NULL_PAGE].set(0.0)
    table = jnp.asarray([[2, NULL_PAGE], [3, 4]], jnp.int32)
    g = gather_pages(pages, table)
    assert g.shape == (2, 8, 2, 3)
    np.testing.assert_array_equal(np.asarray(g[0, 4:]), 0.0)
    np.testing.assert_array_equal(np.asarray(g[0, :4]), np.asarray(pages[2]))


def test_fused_flash_decode_matches_reference():
    """The fused kernel (posit decode -> log-domain QK -> online softmax ->
    PV -> f32 out) in interpret mode stays within quantization distance of
    the exact gather reference on a posit-8 cache."""
    rng = np.random.default_rng(7)
    B, KV, group, hd, ps, nlp = 2, 2, 2, 16, 8, 2
    pcc = P.POSIT8
    # width-16 log-domain dots over the posit-8 cache: the serving shape.
    # (width-8 dots are a coarser approximation — their distance from the
    # exact dot is real quantization error, not a kernel defect)
    cfg = EulerConfig(width=16, mode="euler", stages=2)
    num_pages = 2 + RESERVED_PAGES + B * nlp
    kf = rng.standard_normal((num_pages, ps, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((num_pages, ps, KV, hd)).astype(np.float32)
    kf[NULL_PAGE] = kf[TRASH_PAGE] = 0.0
    vf[NULL_PAGE] = vf[TRASH_PAGE] = 0.0
    k_pages = P.to_storage(P.encode_from_float(jnp.asarray(kf), pcc), pcc)
    v_pages = P.to_storage(P.encode_from_float(jnp.asarray(vf), pcc), pcc)
    table = jnp.asarray([[2, 3], [4, NULL_PAGE]], jnp.int32)
    pos = jnp.asarray([11, 5], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, KV * group, hd)), jnp.float32)
    ref = paged_attention_reference(q, k_pages, v_pages, table, pos, pc=pcc)
    for window in (None, 6):
        out = paged_flash_decode(q, k_pages, v_pages, table, pos,
                                 window, pc=pcc, cfg_qk=cfg, cfg_pv=cfg,
                                 interpret=True)
        refw = paged_attention_reference(q, k_pages, v_pages, table, pos,
                                         pc=pcc, window=window)
        assert out.shape == refw.shape == (B, 1, KV * group * hd)
        diff = float(jnp.max(jnp.abs(out - refw)))
        assert diff < 0.05, (window, diff)
        assert float(jnp.max(jnp.abs(out))) > 0.0
    assert float(jnp.max(jnp.abs(ref))) > 0.0


# ---------------------------------------------------------------------------
# model level: decode_step paged == dense, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,cache_dtype", [
    ("exact", jnp.float32),
    ("lax_ref", jnp.uint8),
    ("pallas", jnp.uint8),
])
def test_decode_step_paged_matches_dense(model_params, backend, cache_dtype):
    """Reference backends: paged logits bit-identical to dense, greedy
    tokens included.  On pallas the paged side runs the fused flash-decode
    kernel over the posit-word pages, so both sides are fed the dense
    tokens and the logits agree within the kernel's bound against the
    gather reference (test_fused_flash_decode_matches_reference)."""
    m, params, fctx = model_params
    fused = backend == "pallas"
    ctx = fctx if backend == "exact" else _euler_ctx(backend)[0]
    B, max_len, ps, Tp = 2, 32, 8, 8
    rng = np.random.default_rng(3)
    prompts = jnp.asarray(rng.integers(1, CFG.vocab, (B, Tp)), jnp.int32)
    dense = m.init_cache(B, max_len, cache_dtype)
    logits, dense = m.prefill(params, prompts, ctx, dense)
    # hand-built pool: slot0 -> page 2, slot1 -> page 3; growth pages 4/5
    # (zeroed); remaining table entries NULL
    num_pages = 6
    pool = {kk: jnp.zeros((CFG.n_layers, num_pages, ps) + dense[kk].shape[3:],
                          dense[kk].dtype) for kk in ("k", "v")}
    for kk in ("k", "v"):
        pool[kk] = pool[kk].at[:, 2].set(dense[kk][:, 0, :ps])
        pool[kk] = pool[kk].at[:, 3].set(dense[kk][:, 1, :ps])
    table = jnp.asarray([[2, 4, 0, 0], [3, 5, 0, 0]], jnp.int32)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    tok_p, paged = tok, pool
    pos = jnp.full((B,), Tp, jnp.int32)
    for _ in range(6):
        ld, dense = m.decode_step(params, tok, pos, dense, ctx)
        lp, paged = m.decode_step(params, tok_p, pos, paged, ctx,
                                  page_table=table)
        tok = jnp.argmax(ld, -1).astype(jnp.int32)
        if fused:
            np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                       rtol=0, atol=0.05)
            tok_p = tok
        else:
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
            tok_p = jnp.argmax(lp, -1).astype(jnp.int32)
            np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_p))
        pos = pos + 1


# ---------------------------------------------------------------------------
# scheduler level: full drains bit-identical under co-scheduling + refill
# ---------------------------------------------------------------------------

def _drain(eng, prompts, gen, buckets):
    b = RequestBatcher(eng, prompt_buckets=buckets)
    for p in prompts:
        b.submit(p, max_new=gen.max_new_tokens)
    return b.run(gen, key=jax.random.PRNGKey(1)), b


@pytest.mark.parametrize("backend,cache_dtype", [
    ("exact", jnp.float32),
    ("lax_ref", jnp.uint8),
])
def test_batcher_paged_matches_dense_with_refills(model_params, backend,
                                                  cache_dtype):
    """Per-request tokens bit-identical between the paged pool and the
    dense bucketed baseline, under co-scheduling AND mid-stream refill.
    The dense baseline buckets at every page multiple so both arms pack
    prompts identically; euler numerics makes this a byte-level cache
    equivalence test (per-tensor pre_scale sees every slot's rows)."""
    m, params, fctx = model_params
    ctx = fctx if backend == "exact" else _euler_ctx(backend)[0]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab,
                            int(rng.integers(3, 30))).astype(np.int32)
               for _ in range(6)]
    gen = GenerationConfig(max_new_tokens=7)
    buckets = tuple(range(8, 64, 8))
    eng_d = ServeEngine(m, params, ctx, max_len=64, batch=2,
                        cache_dtype=cache_dtype)
    eng_p = ServeEngine(m, params, ctx, max_len=64, batch=2,
                        cache_dtype=cache_dtype,
                        paged=PagedKVConfig(page_size=8))
    res_d, bd = _drain(eng_d, prompts, gen, buckets)
    res_p, bp = _drain(eng_p, prompts, gen, buckets)
    assert bd.stats["refills"] >= 1  # co-scheduling + mid-stream refill
    assert set(res_d) == set(res_p)
    for rid in res_d:
        np.testing.assert_array_equal(res_d[rid], res_p[rid])
    # paged actually paged: the pool never needed full dense occupancy
    assert eng_p.kv.peak_pages < 2 * eng_p.kv.n_logical


# ---------------------------------------------------------------------------
# pool pressure: backpressure + preemption keep correctness
# ---------------------------------------------------------------------------

def test_oom_backpressure_holds_admission(model_params):
    """An undersized pool rejects admissions with kv_oom backpressure
    events, but every request still completes with its full budget."""
    m, params, ctx = model_params
    eng = ServeEngine(m, params, ctx, max_len=64, batch=4,
                      cache_dtype=jnp.float32,
                      paged=PagedKVConfig(page_size=8, num_pages=11))
    b = RequestBatcher(eng)
    rng = np.random.default_rng(2)
    for _ in range(4):
        b.submit(rng.integers(1, CFG.vocab, 24).astype(np.int32), max_new=4)
    res = b.run(GenerationConfig(max_new_tokens=4))
    assert len(res) == 4 and all(len(v) == 4 for v in res.values())
    assert b.stats["kv_oom"] >= 1  # the pool really was too small

def test_growth_preemption_recomputes_identically(model_params):
    """Decode growth on a dry pool preempts the youngest slot; the victim
    re-runs from scratch and (greedy) emits exactly the tokens of an
    unpressured run."""
    m, params, ctx = model_params
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, CFG.vocab, 8).astype(np.int32)
               for _ in range(3)]
    gen = GenerationConfig(max_new_tokens=30)

    def run(num_pages):
        eng = ServeEngine(m, params, ctx, max_len=64, batch=2,
                          cache_dtype=jnp.float32,
                          paged=PagedKVConfig(page_size=8,
                                              num_pages=num_pages))
        b = RequestBatcher(eng)
        for p in prompts:
            b.submit(p, max_new=30)
        return b.run(gen, key=jax.random.PRNGKey(3)), b

    res_big, _ = run(2 * 8 + 3)                   # roomy: no pressure
    res_small, b_small = run(11)                  # 9 usable pages for 2 slots
    assert b_small.stats["preempts"] >= 1
    assert set(res_big) == set(res_small)
    for rid in res_big:
        np.testing.assert_array_equal(res_big[rid], res_small[rid])


# ---------------------------------------------------------------------------
# failover: snapshot/resume carries the page tables
# ---------------------------------------------------------------------------

def test_paged_kill_and_restore_tokens_identical(model_params, tmp_path):
    """A paged drain killed mid-stream and resumed on a FRESH engine (pool
    bytes + page tables restored from disk) finishes every request with
    exactly the tokens of an uninterrupted run."""
    m, params, ctx = model_params
    gen = GenerationConfig(max_new_tokens=8)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, CFG.vocab, int(rng.integers(3, 20)))
               for _ in range(5)]

    def engine():
        return ServeEngine(m, params, ctx, max_len=64, batch=2,
                           cache_dtype=jnp.float32,
                           paged=PagedKVConfig(page_size=8))

    base_b = RequestBatcher(engine())
    for p in prompts:
        base_b.submit(p, max_new=8)
    base = base_b.run(gen, key=jax.random.PRNGKey(11))

    b1 = DurableBatcher(engine(), ckpt_dir=str(tmp_path), snapshot_every=1)
    for p in prompts:
        b1.submit(p, max_new=8)
    partial = b1.run(gen, key=jax.random.PRNGKey(11), max_steps=3)  # kill -9
    assert len(partial) < len(base)
    b2 = DurableBatcher(engine(), ckpt_dir=str(tmp_path), snapshot_every=1)
    res = b2.resume()
    assert set(res) == set(base)
    for rid in base:
        np.testing.assert_array_equal(np.asarray(res[rid]),
                                      np.asarray(base[rid]))
    # the restored mapping is live, not just readable: pool accounting
    # drained back to zero after the resumed drain retired everything
    assert b2.engine.kv.alloc.used_count >= 0


def test_paged_snapshot_rejects_dense_engine(model_params, tmp_path):
    m, params, ctx = model_params
    b1 = DurableBatcher(ServeEngine(m, params, ctx, max_len=64, batch=2,
                                    cache_dtype=jnp.float32,
                                    paged=PagedKVConfig(page_size=8)),
                        ckpt_dir=str(tmp_path), snapshot_every=1)
    b1.submit(np.arange(1, 9, dtype=np.int32), max_new=6)
    b1.run(GenerationConfig(max_new_tokens=6), max_steps=2)
    dense_eng = ServeEngine(m, params, ctx, max_len=64, batch=2,
                            cache_dtype=jnp.float32)
    b2 = DurableBatcher(dense_eng, prompt_buckets=(8, 16),
                        ckpt_dir=str(tmp_path), snapshot_every=1)
    with pytest.raises(RuntimeError, match="layout mismatch"):
        b2.resume()


# ---------------------------------------------------------------------------
# admission: over-max_len prompts are rejected, not truncated
# ---------------------------------------------------------------------------

def test_paged_long_prompt_rejected_not_truncated(model_params):
    m, params, ctx = model_params
    eng = ServeEngine(m, params, ctx, max_len=64, batch=2,
                      cache_dtype=jnp.float32, paged=PagedKVConfig(page_size=8))
    b = RequestBatcher(eng)
    rid_long = b.submit(np.arange(100, dtype=np.int32) % CFG.vocab,
                        max_new=4)
    rid_ok = b.submit(np.arange(10, dtype=np.int32) % CFG.vocab, max_new=4)
    res = b.run(GenerationConfig(max_new_tokens=4))
    assert b.statuses[rid_long] == "rejected"
    assert len(res[rid_long]) == 0
    assert b.stats["rejected"] == 1 and b.stats["truncated"] == 0
    assert b.statuses[rid_ok] == "ok" and len(res[rid_ok]) == 4
