"""Slot-based continuous-batching serving.

The serving layer is built around two invariants that make the classic
serving-loop bug class (ignored EOS, bucket-overflow corruption, stale
caches) structurally impossible:

* **Explicit cache lifecycle.**  ``ServeEngine`` owns the stacked KV/SSM
  cache and exposes ``reset_all`` / ``reset_slot`` (backed by the model
  cache API, ``Model.reset_cache``).  ``generate`` resets the whole cache
  before prefill; the scheduler resets a slot before refilling it, so no
  state survives a request.

* **Per-slot device state.**  Every batch row ("slot") carries its own
  position, so prompts of different lengths decode side by side and a
  finished slot is refilled *at step granularity* while its neighbours
  keep decoding (``Model.decode_step`` accepts a [B] position vector).

``ServeEngine.generate`` keeps its whole-batch signature: EOS-aware decode
that masks finished rows to ``pad_id`` and early-exits (host-checked in
chunks of ``decode_chunk`` on-device steps) once every row is done.

``RequestBatcher`` is the host-side scheduler.  Request lifecycle::

    queued -> prefill (slot admission, batch-1, own bucket) -> decoding
           -> done (EOS | max_new budget) -> slot refilled from the queue

Prompts are bucketed per *request* (not per batch group), so a request's
tokens are independent of whichever other requests it was co-scheduled
with; a prompt longer than the largest bucket is truncated to its last
``bucket`` tokens with a logged warning (never a negative-offset slice).
Prompts longer than the engine's ``max_len`` are never truncated: they are
rejected at admission with terminal status ``"rejected"``.

**Paged mode** (``ServeEngine(..., paged=PagedKVConfig(...))``) replaces
the per-slot bucketed cache rows with a shared page pool
(``serving.kvcache``): prefill allocates ``ceil(len/page_size)`` pages,
decode grows one page at a time as a slot crosses page boundaries, and
retire returns the pages to the pool at the next refill.  Cache HBM then
scales with what requests actually use instead of ``batch * max_len``,
and a prompt of any length up to ``max_len`` is admitted unbucketed.
Pool exhaustion surfaces as ``PagePoolOOM``: the batcher reclaims retired
slots' deferred pages, then preempts the youngest-admitted slot (its
request re-enqueues at the queue front and recomputes from scratch), and
finally holds admission (queue backpressure).

**Observability.**  The loop's layers open ``jax.profiler`` spans
(``serving.spans``: admission, prefill, page growth, decode step and its
page-table upload and device wait, retire, the completion callback), which
any profiler capture records on the device ops' clock, and
``RequestBatcher.stats`` counts prefills, pages grown and programs compiled
per drain, the weights the engine holds as posit words with the
contractions that read them, the ``logmac`` grid steps and 128x128
sub-tile products its programs run (``logmac_steps``, ``logmac_tiles``),
and, for a model with experts, the
token-expert pairs routed to the experts held (``expert_rows``) and the
rows those experts computed (``expert_slots``), counted on the device and
read with each call's tokens.

**Weights.**  A serving weight is a constant: on a backend that reads
posit words (``pallas``), ``ServeEngine`` encodes each weight a
contraction reads whole to words of its primary format once, when it takes
the weights (``engine.params = tree``), and its programs read those words
(``numerics.stored``) instead of scaling and encoding every weight at every
step.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import Ctx
from repro.numerics import NumericsContext, get_backend
from repro.numerics import stored
from repro.reliability.faults import FaultPlan
from repro.reliability import faults as _faults
from repro.serving import spans
from repro.serving.kvcache import PagePoolOOM, PagedKVCache, PagedKVConfig

log = logging.getLogger("repro.serving")


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => no top-k filter
    eos_id: int | None = None         # stop a row once it emits this token
    pad_id: int = 0                   # what finished rows emit afterwards


def _sample(logits, gen: GenerationConfig, key):
    """Greedy / temperature / top-k sampling of one [B, V] logits slab."""
    if gen.temperature == 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / gen.temperature
    if gen.top_k:
        kth = jax.lax.top_k(logits, gen.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


# The serving path's programs, built from named functions: a program's XLA
# module is named after its function (``jit_kv_scatter``) in a trace.

def kv_scatter(cache, slab_cache, pages):
    """Scatter a batch-1 prefill slab into the slot's physical pages."""
    return jax.tree.map(
        lambda pool, slab: pool.at[:, pages].set(
            slab[:, 0].reshape((slab.shape[0], pages.shape[0], -1)
                               + slab.shape[3:]).astype(pool.dtype)),
        cache, slab_cache)


def kv_zero_page(cache, page):
    """Zero one pool page.  Growth pages must be zeroed: a reused page
    carries the previous tenant's words, and per-tensor pre_scale sees
    gathered garbage."""
    return jax.tree.map(lambda pool: pool.at[:, page].set(0), cache)


def slot_write(cache, slab_cache, slot):
    """Write a batch-1 prefill cache into row ``slot`` of the dense cache."""
    return jax.tree.map(
        lambda a, b: jax.lax.dynamic_update_slice_in_dim(
            a, b.astype(a.dtype), slot, axis=1), cache, slab_cache)


# the pool is donated: a page write updates it in place.  Undonated, each
# call copies the whole pool, and the copies of calls queued on the device
# are held at once: with the weights' posit words beside the float tree
# they took the chip past 93% of its memory
_kv_scatter = jax.jit(kv_scatter, donate_argnums=0)
_kv_zero_page = jax.jit(kv_zero_page, donate_argnums=0)
_slot_write = jax.jit(slot_write)


# what each program's trace counts: held-weight reads and logmac's grid
_TALLY = (stored.STORED, stored.PER_CALL, stored.LOGMAC_STEPS,
          stored.LOGMAC_TILES)


def _prefill_program(model, ctx: Ctx, record: Callable):
    def serve_prefill(params, toks, cache):
        with stored.tally(_TALLY) as reads:
            out = model.prefill(params, toks, ctx, cache, with_counts=True)
        record("serve_prefill", reads)
        return out
    return jax.jit(serve_prefill)


class ServeEngine:
    def __init__(self, model, params, ctx: Ctx | None = None, *,
                 max_len: int = 2048, batch: int = 8, cache_dtype=None,
                 decode_chunk: int = 8,
                 numerics: NumericsContext | None = None,
                 fault: FaultPlan | None = None,
                 levels: "Sequence[NumericsContext] | None" = None,
                 paged: PagedKVConfig | None = None):
        """``numerics`` (policy + backend) overrides whatever the ctx
        carries — the serving-time precision/backend switch.  With no ctx at
        all, one is derived from the model's own numerics.

        ``decode_chunk``: how many decode steps ``generate`` scans on-device
        between host-side all-done checks (the early-exit granularity).

        ``fault``: optional live fault-injection plan.  Decode steps run
        under ``reliability.faults.inject`` with a per-step key derived from
        the plan's seed and a fault-step counter carried through the decode
        scan — effective when the numerics backend is a ``faulty:<base>``
        wrapper.  Prefill is never corrupted (faults target the decode
        datapath where tokens are produced).  Reassigning ``self.fault``
        between runs is safe: the jitted scans are cached per plan.

        ``levels``: optional precision ladder for per-slot degradation —
        ``levels[0]`` is the engine's primary numerics (it overrides the
        ``numerics`` argument; highest precision), later entries are the
        progressively cheaper contexts the scheduler demotes slots to under
        load.  Slots at different ladder levels decode side by side: each
        decode step runs one masked scan per *occupied* level and merges
        caches/tokens per slot, so a slot's stream only ever sees its own
        level's numerics.  With one level (or none given) the decode path is
        byte-for-byte the single-context path.

        ``paged``: switch the KV cache to the paged pool layout
        (``serving.kvcache``).  The engine then owns a ``PagedKVCache``
        (``self.kv``), the cache pytree holds the shared per-layer page
        pools instead of per-slot rows, and decode runs through the
        ``decode_attention`` numerics op (the fused flash-decode Pallas
        kernel on TPU).  ``generate`` is unavailable in paged mode — serve
        through ``RequestBatcher``.  Dense-family models only."""
        if levels:
            numerics = levels[0]
        if ctx is None:
            ctx = (model.make_ctx() if hasattr(model, "make_ctx")
                   else Ctx(numerics=numerics))
        if numerics is not None:
            ctx = dataclasses.replace(ctx, numerics=numerics,
                                      ecfg=numerics.policy.default)
        self.model = model
        self.ctx = ctx
        self.max_len = max_len
        self.batch = batch
        self.decode_chunk = max(1, decode_chunk)
        self.paged = paged
        self._cache_dtype = cache_dtype
        if paged is not None:
            if max_len % paged.page_size:
                raise ValueError(
                    f"max_len={max_len} not a multiple of "
                    f"page_size={paged.page_size}")
            num_pages = paged.resolve_pages(batch, max_len)
            self.kv = PagedKVCache(batch, max_len, paged.page_size, num_pages)
            self.cache = model.init_paged_cache(num_pages, paged.page_size,
                                                cache_dtype)
            self._cache1 = None
            # zero batch-1 dense templates for paged prefills, one per
            # page-padded prompt length (never mutated: prefill is
            # functional, so these stay all-zeros)
            self._ptmpl: dict[int, Any] = {}
            self._scatter_fn = _kv_scatter
            self._zero_page_fn = _kv_zero_page
        else:
            self.kv = None
            self.cache = model.init_cache(batch, max_len, cache_dtype)
            # zero batch-1 cache template for slot prefills (never mutated:
            # prefill is functional, so this stays all-zeros)
            self._cache1 = model.init_cache(1, max_len, cache_dtype)
        # the precision ladder: _ctxs[0] is the primary ctx; every further
        # level reuses it with only the numerics (and its default ecfg)
        # swapped, so model wiring is identical across levels
        self._ctxs = [ctx] + [
            dataclasses.replace(ctx, numerics=nc, ecfg=nc.policy.default)
            for nc in (levels or [])[1:]]
        # (program, ladder level) -> (weights read as stored words,
        # weights encoded per call), recorded when the program traces
        self.weight_reads: dict[tuple[str, int], tuple[int, int]] = {}
        # (program, ladder level) -> (logmac grid steps, sub-tile products)
        self.logmac_grid: dict[tuple[str, int], tuple[int, int]] = {}
        self._prefill_fns = {
            lvl: _prefill_program(
                model, c, functools.partial(self._record_reads, level=lvl))
            for lvl, c in enumerate(self._ctxs)}
        self._prefill = self._prefill_fns[0]
        self._reset = self._reset_slot = jax.jit(model.reset_cache)
        self._write_slot_fn = _slot_write
        self._scan_cache: dict[tuple, Any] = {}
        self.last_decode_steps = 0  # decode steps run by the last generate
        self.fault = fault
        self.fault_step = 0  # decode-step counter for step_slots fault keys
        # expert counts of the last prefill or decode step (moe_apply's
        # expert_rows, expert_slots), read with its tokens
        self.last_counts = (0, 0)
        self.n_levels = len(self._ctxs)
        self.params = params

    # -- the weights ------------------------------------------------------

    @property
    def params(self):
        """The float weight tree, as given: the embedding gather, the
        non-pallas backends and any outside reference read it."""
        return self._params

    @params.setter
    def params(self, params):
        """Take a weight tree.  When the primary context runs a backend
        that reads posit words in euler mode, every weight a contraction
        reads whole is encoded here, once, to words of the primary
        format (``numerics.stored``); the programs then read ``served``,
        which holds them beside the float leaves."""
        self._params = self.served = None  # the old words go first
        self.word_leaves = self.word_bytes = 0
        if params is not None:
            self.served = self._hold(params)
        self._params = params

    def _hold(self, params):
        nctx = self._ctxs[0].numerics
        cfg = nctx.policy.default
        hold_weights = getattr(self.model, "hold_weights", None)
        if (hold_weights is None or not get_backend(nctx.backend).reads_words
                or cfg.mode != "euler" or not cfg.pre_scale):
            return params
        with spans.span(spans.ENCODE_WEIGHTS):
            served = hold_weights(params, cfg.posit)
            jax.block_until_ready(served)
        held = stored.held(served)
        self.word_leaves = len(held)
        self.word_bytes = sum(int(w.words.nbytes) for w in held)
        log.info("holding %d weights as %s words: %d bytes",
                 self.word_leaves, cfg.posit.name, self.word_bytes)
        return served

    def _record_reads(self, program: str, reads: dict, level: int):
        key = (program, level)
        counts = (reads[stored.STORED], reads[stored.PER_CALL])
        grid = (reads[stored.LOGMAC_STEPS], reads[stored.LOGMAC_TILES])
        if (self.weight_reads.get(key), self.logmac_grid.get(key)) != (
                counts, grid):
            self.weight_reads[key] = counts
            self.logmac_grid[key] = grid
            log.info("%s at level %d reads %d weights as stored words, "
                     "encodes %d per call; logmac runs %d grid steps, "
                     "%d sub-tile products", program, level, *counts, *grid)

    # -- cache lifecycle ------------------------------------------------

    def reset_all(self):
        """Invalidate every slot (used at the top of every generate/run)."""
        if self.kv is not None:
            self.kv.reset()
            self.cache = jax.tree.map(jnp.zeros_like, self.cache)
            return
        self.cache = self._reset(self.cache)

    def reset_slot(self, slot: int):
        """Invalidate one slot (used when the scheduler retires a request)."""
        if self.kv is not None:
            self.kv.free_slot(slot)  # pool rows are overwritten on reuse
            return
        self.cache = self._reset_slot(self.cache, jnp.int32(slot))

    def release_slot(self, slot: int):
        """Return a slot's pages to the pool (dense engines: no-op).

        The batcher calls this on preemption/reclaim; ordinary retires keep
        the pages mapped until the refilling prefill frees them, so retired
        slots' masked decode writes keep landing at their frozen position —
        byte-identical to the dense engine's behavior (a per-tensor
        ``pre_scale`` couples slots, so euler-mode bit-parity with dense
        needs even retired rows' cache bytes to match)."""
        if self.kv is not None and self.kv.n_pages(slot):
            self.kv.free_slot(slot)

    def ensure_slot_pages(self, slot: int, pos) -> list:
        """Grow ``slot`` until its pages cover a cache write at ``pos``.

        Every grown page is zeroed before it becomes gatherable.  Raises
        :class:`PagePoolOOM` mid-growth with all already-grown pages mapped
        and zeroed (consistent state — the batcher preempts and retries).
        Returns the newly-grown physical pages."""
        need = min(int(pos), self.max_len - 1) // self.kv.page_size + 1
        grown = []
        while self.kv.n_pages(slot) < need:
            p = self.kv.grow_slot(slot)
            self.cache = self._zero_page_fn(self.cache, jnp.int32(p))
            grown.append(p)
        return grown

    # -- jitted decode programs -----------------------------------------

    def _decode_scan(self, gen: GenerationConfig, n: int, level: int = 0):
        """n masked decode steps, scanned on-device.

        Carry: (tok [B], pos [B], done [B], cache, key, fstep).  Finished
        rows emit ``pad_id``, keep their position frozen and their sampled
        token replaced — so a done row can never advance or influence its
        own stream again.  Active rows clamp position writes to max_len-1
        (dynamic_update_slice would clamp anyway; being explicit keeps the
        cache write location well-defined).  ``fstep`` is the global decode
        step index driving the fault-injection window/keys; it advances even
        with no fault plan so the carry structure is uniform."""
        cache_key = (gen.temperature, gen.top_k, gen.eos_id, gen.pad_id, n,
                     self.fault, level)
        if cache_key in self._scan_cache:
            return self._scan_cache[cache_key]
        pad = jnp.int32(gen.pad_id)
        eos = gen.eos_id
        maxpos = self.max_len - 1
        model, ctx, fault = self.model, self._ctxs[level], self.fault
        paged = self.kv is not None

        def step_kwargs(*a):
            # paged scans thread (page_table, write_mask) through the model;
            # the mask is all-True on the single-level path so masked (done)
            # rows still write their pad-token k/v at their frozen position,
            # exactly like the dense cache does — per-tensor pre_scale makes
            # that byte-level detail observable.
            return ({"page_table": a[0], "write_mask": a[1]} if paged
                    else {})

        def serve_decode(params, tok, pos, done, cache, key, fstep,
                         *paged_args):
            def body(carry, _):
                tok, pos, done, cache, key, fstep = carry
                key, sub = jax.random.split(key)
                kw = step_kwargs(*paged_args)
                if fault is not None:
                    fkey = jax.random.fold_in(
                        jax.random.PRNGKey(fault.seed), fstep)
                    with _faults.inject(fault, fkey, fstep):
                        logits, cache, counts = model.decode_step(
                            params, tok, pos, cache, ctx, with_counts=True,
                            **kw)
                else:
                    logits, cache, counts = model.decode_step(
                        params, tok, pos, cache, ctx, with_counts=True, **kw)
                nxt = _sample(logits, gen, sub)
                nxt = jnp.where(done, pad, nxt)
                pos = jnp.where(done, pos, jnp.minimum(pos + 1, maxpos))
                if eos is not None:
                    done = done | (nxt == eos)
                return (nxt, pos, done, cache, key, fstep + 1), (nxt, counts)

            with stored.tally(_TALLY) as reads:
                carry, toks = jax.lax.scan(
                    body, (tok, pos, done, cache, key, fstep), None,
                    length=n)
            self._record_reads("serve_decode", reads, level)
            return carry, toks

        fn = jax.jit(serve_decode)
        self._scan_cache[cache_key] = fn
        return fn

    # -- whole-batch generation (legacy API, now EOS-aware) -------------

    def generate(self, prompts, gen: GenerationConfig, key=None):
        """prompts: [B, Tp] int32 (right-aligned in fixed buckets).

        Returns tokens [B, max_new_tokens].  With ``gen.eos_id`` set, a row
        stops at (and including) its first EOS and emits ``gen.pad_id``
        afterwards; the decode loop early-exits once every row is done (the
        output is still padded to the full [B, max_new_tokens] shape)."""
        if self.kv is not None:
            raise RuntimeError(
                "generate() is whole-batch/bucketed; a paged engine serves "
                "through RequestBatcher (prefill_slot/step_slots)")
        B, Tp = prompts.shape
        assert B == self.batch
        if gen.max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        self.reset_all()  # no state from a previous generate can leak in
        logits, cache, _ = self._prefill(self.served, prompts, self.cache)
        key, sub = jax.random.split(key)
        tok = _sample(logits, gen, sub)
        done = (tok == gen.eos_id if gen.eos_id is not None
                else jnp.zeros((B,), bool))
        pos = jnp.full((B,), Tp, jnp.int32)
        outs = [tok[:, None]]  # first token comes from the prefill logits
        remaining = gen.max_new_tokens - 1
        steps = 0
        fstep = jnp.int32(0)
        while remaining > 0 and not bool(done.all()):
            n = min(self.decode_chunk, remaining)
            scan = self._decode_scan(gen, n)
            (tok, pos, done, cache, key, fstep), (toks, _) = scan(
                self.served, tok, pos, done, cache, key, fstep)
            outs.append(toks.T)  # [B, n]
            remaining -= n
            steps += n
        self.cache = cache
        self.last_decode_steps = steps
        out = jnp.concatenate(outs, axis=1)
        if out.shape[1] < gen.max_new_tokens:  # early exit: pad to contract
            out = jnp.pad(out, ((0, 0), (0, gen.max_new_tokens - out.shape[1])),
                          constant_values=gen.pad_id)
        return out

    # -- slot-level primitives (used by the scheduler) -------------------

    def prefill_slot(self, slot: int, prompt_tokens, gen: GenerationConfig,
                     key, level: int = 0) -> int:
        """Prefill one request into ``slot`` and return its first token.

        Runs a batch-1 prefill over the request's own bucket on a zero
        cache and writes the resulting cache into the slot.  The write is a
        FULL overwrite of every cache leaf's slot row (KV slabs, SSM state,
        conv tail), i.e. it subsumes ``reset_slot`` — that is what makes
        stale-state leaks into a refilled slot impossible.  ``level`` picks
        the precision-ladder context the request was admitted at.

        Paged engines prefill into a zero length-``len(prompt_tokens)``
        dense template (the length must be a page multiple — the batcher
        pads to one) and scatter the resulting slab into freshly-allocated
        pool pages; the previous tenant's deferred pages are freed first.
        Raises :class:`PagePoolOOM` (slot left unmapped, pool state clean)
        when the pool cannot hold the request plus one growth page."""
        with spans.span(spans.PREFILL, slot=slot,
                        length=len(prompt_tokens)) as sp:
            toks = jnp.asarray(prompt_tokens, jnp.int32)[None, :]
            if self.kv is not None:
                ps = self.kv.page_size
                Tpad = toks.shape[1]
                if Tpad % ps or Tpad > self.max_len:
                    raise ValueError(
                        f"paged prefill length {Tpad} must be a multiple of "
                        f"page_size={ps} and <= max_len={self.max_len}")
                if self.kv.n_pages(slot):
                    self.kv.free_slot(slot)
                pages = self.kv.alloc_slot(slot, Tpad // ps)
                tmpl = self._ptmpl.get(Tpad)
                if tmpl is None:
                    tmpl = self.model.init_cache(1, Tpad, self._cache_dtype)
                    self._ptmpl[Tpad] = tmpl
                logits, c1, counts = self._prefill_fns[level](
                    self.served, toks, tmpl)
                self.cache = self._scatter_fn(self.cache, c1,
                                              jnp.asarray(pages, jnp.int32))
            else:
                logits, c1, counts = self._prefill_fns[level](
                    self.served, toks, self._cache1)
                self.cache = self._write_slot_fn(self.cache, c1,
                                                 jnp.int32(slot))
            with spans.span(spans.PREFILL_WAIT):
                first, counts = jax.device_get(
                    (_sample(logits, gen, key)[0], counts))
            self._take_counts([counts], sp)
            return int(first)

    def _take_counts(self, counts, sp):
        """Keep a call's expert counts, host copies of one dict per program
        it ran, as ``last_counts`` and on its span."""
        self.last_counts = tuple(
            sum(int(np.sum(c[k])) for c in counts)
            for k in ("expert_rows", "expert_slots"))
        sp.set_metadata(expert_rows=self.last_counts[0],
                        expert_slots=self.last_counts[1])

    def _wait(self, toks, counts, sp) -> np.ndarray:
        """Bring a step's emitted tokens and the expert counts of the scans
        that made them to the host: the wait for the device."""
        with spans.span(spans.DECODE_WAIT):
            toks, counts = jax.device_get((toks, counts))
        self._take_counts(counts, sp)
        return toks

    @staticmethod
    def _slot_mask(m, leaf):
        """Broadcast a [B] slot mask over a cache leaf (slot axis = 1)."""
        return m.reshape((1, -1) + (1,) * (leaf.ndim - 2))

    def _table_cap(self) -> int:
        """Logical-page window for this step's device table: the max mapped
        page count over all slots, rounded up to a power of two (so jit
        retraces O(log n_logical) table widths, not one per length), capped
        at ``n_logical``."""
        n = max(max((self.kv.n_pages(s) for s in range(self.batch)),
                    default=1), 1)
        cap = 1
        while cap < n:
            cap *= 2
        return min(cap, self.kv.n_logical)

    def step_slots(self, gen: GenerationConfig, tok, pos, active, key,
                   level=None):
        """One masked decode step over all slots.

        ``tok``/``pos``: [B] host arrays; ``active``: [B] bool.  Inactive
        slots are fed as done (emit pad, frozen position).  Returns the
        emitted [B] tokens (numpy) and the threaded PRNG key; the cache
        advances on the engine, as does ``fault_step`` (the scheduler-path
        decode-step counter for fault-injection keys).

        ``level``: optional [B] precision-ladder indices.  When every active
        slot shares one level this is exactly one masked scan (the fast
        path, bit-identical to the level-free call); mixed levels run one
        scan per occupied level — each from the SAME pre-step cache with the
        other levels' slots masked done — and the caches/tokens are merged
        per slot, so no slot's stream or cache row is ever touched by
        another level's numerics."""
        act = np.asarray(active, bool)
        kv = self.kv
        with spans.span(spans.DECODE, rows=int(act.sum()),
                        live_pages=kv.live_pages if kv else 0,
                        pool_pages=kv.alloc.num_pages if kv else 0) as sp:
            return self._step_slots(gen, tok, pos, act, key, level, sp)

    def _step_slots(self, gen, tok, pos, act, key, level, sp):
        tok = jnp.asarray(tok, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        lvls = (np.zeros(act.shape, np.int32) if level is None
                else np.asarray(level, np.int32))
        used = sorted({int(l) for l, a in zip(lvls, act) if a}) or [0]
        fstep = jnp.int32(self.fault_step)
        if self.kv is not None:
            with spans.span(spans.DECODE_TABLE) as sp:
                cap = self._table_cap()
                sp.set_metadata(cap=cap)
                table = self.kv.table_device()[:, :cap]
            if len(used) == 1:
                # all rows write (mask all-True): done rows land their
                # pad-token k/v at their frozen position like dense does
                scan = self._decode_scan(gen, 1, used[0])
                wmask = jnp.ones(act.shape, bool)
                (_, _, _, cache, key, _), (toks, counts) = scan(
                    self.served, tok, pos, jnp.asarray(~act), self.cache,
                    key, fstep, table, wmask)
                self.cache = cache
                self.fault_step += 1
                return self._wait(toks[0], [counts], sp), key
            # mixed ladder levels: the pool has no slot axis to where-merge
            # over, so levels thread SEQUENTIALLY through it.  Disjointness
            # comes from the write mask: each level's scan writes only its
            # own slots' pages (other rows are redirected to the trash
            # page), so no slot's cache bytes are ever produced by another
            # level's numerics.
            cache, out, counts = self.cache, None, []
            for lvl in used:
                sel = act & (lvls == lvl)
                scan = self._decode_scan(gen, 1, lvl)
                m = jnp.asarray(sel)
                (_, _, _, cache, key, _), (toks, c) = scan(
                    self.served, tok, pos, jnp.asarray(~sel), cache, key,
                    fstep, table, m)
                counts.append(c)
                t = toks[0]
                out = t if out is None else jnp.where(m, t, out)
            self.cache = cache
            self.fault_step += 1
            return self._wait(out, counts, sp), key
        if len(used) == 1:
            scan = self._decode_scan(gen, 1, used[0])
            (_, _, _, cache, key, _), (toks, counts) = scan(
                self.served, tok, pos, jnp.asarray(~act), self.cache, key,
                fstep)
            self.cache = cache
            self.fault_step += 1
            return self._wait(toks[0], [counts], sp), key
        base = self.cache
        merged, out, counts = base, None, []
        for lvl in used:
            sel = act & (lvls == lvl)
            scan = self._decode_scan(gen, 1, lvl)
            (_, _, _, cache_l, key, _), (toks, c) = scan(
                self.served, tok, pos, jnp.asarray(~sel), base, key, fstep)
            counts.append(c)
            m = jnp.asarray(sel)
            merged = jax.tree.map(
                lambda a, b, m=m: jnp.where(self._slot_mask(m, a), b, a),
                merged, cache_l)
            t = toks[0]
            out = t if out is None else jnp.where(m, t, out)
        self.cache = merged
        self.fault_step += 1
        return self._wait(out, counts, sp), key


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    deadline_ms: float | None = None  # wall-clock SLO from submit time
    submit_t: float = 0.0             # batcher-clock timestamp of submit()
    level: int = 0                    # precision-ladder index (0 = highest)
    attempts: int = 0                 # guard-triggered re-enqueues so far
    status: str = "ok"                # ok | timeout | failed | rejected


class QueueFullError(RuntimeError):
    """submit() on a batcher whose queue is at max_queue capacity."""


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """Degradation thresholds for SLO-aware precision throttling.

    Every ``queue_hi`` queued requests push newly-admitted slots one level
    down the engine's precision ladder; a recent-window p99 step latency
    above ``p99_ms`` adds one more.  Levels clamp to the ladder length, so a
    1-level engine never degrades (the config is then inert)."""

    queue_hi: int = 8
    p99_ms: float | None = None
    window: int = 64              # step-latency samples kept for the p99

    def __post_init__(self):
        if self.queue_hi <= 0:
            raise ValueError(f"queue_hi must be > 0, got {self.queue_hi}")
        if self.window <= 0:
            raise ValueError(f"window must be > 0, got {self.window}")


class DegradeController:
    """Maps instantaneous load to an admission precision level.

    Pure policy over observations the batcher feeds it (queue depth at
    admission, per-step wall latency) — it never touches the engine, so the
    demote-on-admission point stays the single place levels are assigned.
    """

    def __init__(self, slo: SLOConfig, n_levels: int):
        self.slo = slo
        self.n_levels = n_levels
        self._lat: list[float] = []

    def record_step(self, dt_ms: float):
        self._lat.append(float(dt_ms))
        if len(self._lat) > self.slo.window:
            del self._lat[:len(self._lat) - self.slo.window]

    def p99_ms(self) -> float:
        if not self._lat:
            return 0.0
        return float(np.percentile(np.asarray(self._lat), 99))

    def admission_level(self, queue_depth: int) -> int:
        lvl = queue_depth // self.slo.queue_hi
        if self.slo.p99_ms is not None and self.p99_ms() > self.slo.p99_ms:
            lvl += 1
        return min(lvl, self.n_levels - 1)


@dataclasses.dataclass
class _Slot:
    """Host-side per-slot scheduler state (device holds tok/pos vectors)."""
    req: Request
    budget: int          # tokens still allowed (per-request max_new cap)
    seq: int = 0         # admission order — preemption evicts the youngest


@dataclasses.dataclass
class _RunState:
    """The scheduler loop's complete host-side state.

    Everything ``run`` needs between two decode steps lives here (the device
    holds the cache on the engine), which is what makes the loop resumable:
    ``serving.failover.DurableBatcher`` serializes this plus the engine cache
    at step boundaries and re-enters ``_drive`` from the restored state."""
    gen: GenerationConfig     # step/sampling config for every decode step
    cap_budget: bool          # True: gen.max_new_tokens caps request budgets
    key: Any                  # threaded PRNG key
    slots: list               # [B] of _Slot | None
    tok: np.ndarray           # [B] last emitted token per slot
    pos: np.ndarray           # [B] next cache write position per slot
    active: np.ndarray        # [B] bool
    step: int = 0             # decode steps taken in this run
    results: dict = dataclasses.field(default_factory=dict)
    level: np.ndarray = None  # [B] per-slot precision-ladder index


_FRESH_STATS = {"steps": 0, "refills": 0, "truncated": 0, "timeouts": 0,
                "guard_retries": 0, "demotions": 0, "rejected": 0,
                "kv_oom": 0, "preempts": 0, "prefills": 0,
                "prefill_tokens": 0, "pages_grown": 0, "compiles": 0,
                "weight_leaves": 0, "weight_bytes": 0, "stored_reads": 0,
                "per_call_reads": 0, "logmac_steps": 0, "logmac_tiles": 0,
                "expert_rows": 0, "expert_slots": 0}


class RequestBatcher:
    """Host-side continuous-batching scheduler over ``ServeEngine`` slots.

    ``submit`` enqueues; ``run`` drains the queue: every free slot is
    admitted (batch-1 prefill fully overwriting the slot), then the whole
    batch decodes one masked step at a time — any slot that finishes (EOS
    or budget) is retired and refilled from the queue *mid-stream*, without
    waiting for the rest of the batch.  Because each request keeps its own
    bucket and position, its tokens are identical to a single-request run.
    """

    def __init__(self, engine: ServeEngine, prompt_buckets=(128, 512, 2048),
                 max_queue: int | None = None, *,
                 slo: SLOConfig | None = None,
                 guard_retry: int = 0, clock: Callable[[], float] = None):
        """``slo``: enable SLO-aware degradation — incoming requests are
        admitted at ``DegradeController.admission_level`` of the engine's
        precision ladder instead of always at level 0.  ``guard_retry``: max
        guard-triggered re-enqueues per request — when the ``guarded:``
        backend reports an *unrecovered* checksum violation on a slot's row,
        the slot is torn down and its request re-enqueued (front of queue)
        one level HIGHER precision; past the bound it retires with status
        "failed".  ``clock``: injectable monotonic-seconds source for
        deadlines/latency (tests pin it; defaults to ``time.monotonic``)."""
        self.engine = engine
        if engine.kv is not None:
            # paged admission pads each prompt to its own page multiple —
            # no buckets, no truncation (over-max_len prompts are rejected)
            self.buckets = None
        else:
            buckets = sorted(b for b in prompt_buckets if b < engine.max_len)
            if not buckets:
                raise ValueError(
                    f"no prompt bucket fits engine max_len={engine.max_len} "
                    f"(got {tuple(prompt_buckets)}); buckets must leave room "
                    f"for at least one generated token")
            if len(buckets) < len(set(prompt_buckets)):
                log.warning("dropping prompt buckets >= max_len=%d: %s",
                            engine.max_len,
                            sorted(set(prompt_buckets) - set(buckets)))
            self.buckets = buckets
        self.max_queue = max_queue
        self.clock = clock if clock is not None else time.monotonic
        self.slo = slo
        self.guard_retry = guard_retry
        self.controller = (DegradeController(slo, engine.n_levels)
                           if slo is not None else None)
        self.queue: list[Request] = []
        self._next_rid = 0
        self._admit_seq = 0  # monotone admission counter (preemption order)
        # ("admit"|"refill"|"done"|"timeout"|"guard_retry", rid, slot, step)
        self.events: list[tuple] = []
        # (time.perf_counter() after the call, "prefill"|"decode",
        # expert_rows, expert_slots) of each call of an expert model
        self.expert_log: list[tuple] = []
        self.stats = dict(_FRESH_STATS)
        self.statuses: dict[int, str] = {}   # rid -> final status

    def submit(self, prompt, max_new: int = 32,
               deadline_ms: float | None = None) -> int:
        """Enqueue a prompt; ``deadline_ms`` is a wall-clock SLO measured
        from now — a request not finished by then retires with status
        "timeout" (partial tokens if it was mid-decode) instead of holding
        its slot."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            raise QueueFullError(
                f"queue full ({len(self.queue)} >= max_queue={self.max_queue})")
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, np.asarray(prompt, np.int32), max_new,
                                  deadline_ms=deadline_ms,
                                  submit_t=self.clock()))
        return rid

    def _expired(self, r: Request, now: float) -> bool:
        return (r.deadline_ms is not None
                and (now - r.submit_t) * 1000.0 > r.deadline_ms)

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _pack(self, r: Request) -> np.ndarray:
        """Right-align the prompt in its own bucket; over-long prompts keep
        their LAST ``bucket`` tokens (recency wins for generation) with a
        logged warning — never a negative-offset slice.

        Paged engines bucket to the prompt's own page multiple instead;
        the admission-time max_len rejection guarantees the prompt fits, so
        the truncation path is dense-only."""
        if self.buckets is None:
            ps = self.engine.kv.page_size
            bucket = max(ps, -(-len(r.prompt) // ps) * ps)
        else:
            bucket = self._bucket(len(r.prompt))
        prompt = r.prompt
        if len(prompt) > bucket:
            log.warning(
                "rid=%d prompt len %d exceeds largest bucket %d; "
                "keeping the last %d tokens", r.rid, len(prompt), bucket, bucket)
            prompt = prompt[-bucket:]
            self.stats["truncated"] += 1
        toks = np.zeros(bucket, np.int32)
        toks[bucket - len(prompt):] = prompt
        return toks

    # -- the scheduler loop ---------------------------------------------

    def run(self, gen: GenerationConfig | None = None,
            on_complete: Callable[[int, np.ndarray], None] | None = None,
            key=None, max_steps: int | None = None):
        """Drain the queue; returns {rid: tokens}.

        ``gen`` supplies sampling/EOS config; per-request token budgets are
        ``min(request.max_new, gen.max_new_tokens)`` (request.max_new alone
        when ``gen`` is None).  ``on_complete(rid, tokens)`` streams each
        request's result the step it finishes.

        ``max_steps`` bounds the decode steps of THIS call; the loop then
        returns the results so far with the full scheduler state retained on
        ``self._state`` — the cooperative-yield / simulated-kill hook used
        by the failover tests and ``serving.failover``."""
        if not self.queue:
            return {}
        st = self._begin(gen, key)
        return self._drive(st, on_complete=on_complete, max_steps=max_steps)

    def _begin(self, gen: GenerationConfig | None, key) -> _RunState:
        """Reset per-drain state (events/stats/cache) and build a fresh
        :class:`_RunState`.  Events/stats describe ONE drain (that is what
        the drivers print), so step indices stay unambiguous across runs."""
        eng = self.engine
        B = eng.batch
        self.events = []
        self.expert_log = []
        self.stats = dict(_FRESH_STATS)
        self.statuses = {}
        eng.reset_all()
        eng.fault_step = 0
        st = _RunState(
            gen=gen if gen is not None else GenerationConfig(),
            cap_budget=gen is not None,
            key=key if key is not None else jax.random.PRNGKey(0),
            slots=[None] * B, tok=np.zeros(B, np.int32),
            pos=np.zeros(B, np.int64), active=np.zeros(B, bool),
            level=np.zeros(B, np.int32))
        self._state = st
        return st

    def _budget(self, st: _RunState, r: Request) -> int:
        return (min(r.max_new, st.gen.max_new_tokens) if st.cap_budget
                else r.max_new)

    def _retire(self, st: _RunState, s: int, on_complete,
                status: str = "ok"):
        slot = st.slots[s]
        r = slot.req
        r.done = True
        r.status = status
        st.results[r.rid] = np.asarray(r.out, np.int32)
        self.statuses[r.rid] = status
        kind = "done" if status == "ok" else status
        self.events.append((kind, r.rid, s, st.step))
        if status == "timeout":
            self.stats["timeouts"] += 1
        if on_complete is not None:
            with spans.span(spans.ON_COMPLETE, rid=r.rid):
                on_complete(r.rid, st.results[r.rid])
        st.slots[s] = None
        st.active[s] = False

    def _complete_unadmitted(self, st: _RunState, r: Request, s: int,
                             on_complete, status: str, tokens=()):
        """Finish a request that never (re)entered a slot — zero-budget
        submissions and queue-expired deadlines."""
        r.done = True
        r.status = status
        st.results[r.rid] = np.asarray(list(tokens), np.int32)
        self.statuses[r.rid] = status
        kind = "done" if status == "ok" else status
        self.events.append((kind, r.rid, s, st.step))
        if status == "timeout":
            self.stats["timeouts"] += 1
        if on_complete is not None:
            with spans.span(spans.ON_COMPLETE, rid=r.rid):
                on_complete(r.rid, st.results[r.rid])

    def _expire_slots(self, st: _RunState, on_complete):
        """Retire every active slot whose deadline has passed — with partial
        tokens and status "timeout".  Neighbour slots are untouched: retire
        only flips this slot's host-side active flag, and the next admission
        fully overwrites the slot's cache row."""
        now = self.clock()
        for s in range(self.engine.batch):
            if st.slots[s] is not None and self._expired(st.slots[s].req, now):
                self._retire(st, s, on_complete, status="timeout")

    def _drain_guard_events(self, st: _RunState, on_complete,
                            prefill_slot: int | None = None):
        """Poll the guarded backend's violation events and re-enqueue any
        slot an UNRECOVERED violation landed on (the op-level escalation
        ladder already absorbed recovered ones).  The re-enqueued request
        restarts from scratch one precision level higher, at the front of
        the queue; after ``guard_retry`` attempts it retires as "failed".
        ``prefill_slot``: attribute batch-1 (prefill-time) events to that
        slot instead of by row index."""
        from repro.numerics import api as _napi
        hit: set[int] = set()
        for ev in _napi.drain_guard_events():
            if not ev.get("unrecovered"):
                continue
            rows = ev.get("rows") or []
            if prefill_slot is not None:
                hit.add(prefill_slot)
            else:
                hit.update(s for s, f in enumerate(
                    rows[:self.engine.batch]) if f)
        for s in sorted(hit):
            if st.slots[s] is None:
                continue
            r = st.slots[s].req
            if r.attempts >= self.guard_retry:
                self._retire(st, s, on_complete, status="failed")
                continue
            r.attempts += 1
            r.level = max(0, r.level - 1)
            r.out = []
            self.events.append(("guard_retry", r.rid, s, st.step))
            self.stats["guard_retries"] += 1
            st.slots[s] = None
            st.active[s] = False
            self.queue.insert(0, r)

    # -- paged-pool pressure handling -----------------------------------

    def _reclaim_retired(self, st: _RunState) -> bool:
        """Free the deferred pages of retired (empty) slots.

        Retired slots keep their pages mapped for dense-write parity (see
        ``ServeEngine.release_slot``); under pool pressure that luxury goes
        first.  Returns True if anything was freed."""
        eng = self.engine
        freed = False
        for s in range(eng.batch):
            if st.slots[s] is None and eng.kv.n_pages(s):
                eng.kv.free_slot(s)
                freed = True
        return freed

    def _preempt_for(self, st: _RunState, grower: int, on_complete) -> bool:
        """Evict the youngest-admitted active slot (≠ ``grower``) so the
        grower can take a page.  The victim's request restarts from scratch
        at the queue front — greedy decoding recomputes the same tokens, so
        preemption costs latency, never correctness."""
        eng = self.engine
        victim, vseq = None, -1
        for s in range(eng.batch):
            if s != grower and st.slots[s] is not None \
                    and st.slots[s].seq > vseq:
                victim, vseq = s, st.slots[s].seq
        if victim is None:
            return False
        r = st.slots[victim].req
        r.out = []
        self.queue.insert(0, r)
        self.events.append(("preempt", r.rid, victim, st.step))
        self.stats["preempts"] += 1
        st.slots[victim] = None
        st.active[victim] = False
        eng.release_slot(victim)
        return True

    def _grow_pages(self, st: _RunState, on_complete):
        """Grow every mapped slot to cover its next cache write (runs right
        before each decode step).  Retired-but-mapped slots grow too — their
        masked pad-token write needs a destination to stay byte-identical
        to dense — but under pressure they are reclaimed, not fought for;
        active slots escalate reclaim -> preempt."""
        eng = self.engine
        grown = 0
        with spans.span(spans.GROW) as sp:
            for s in range(eng.batch):
                if not eng.kv.n_pages(s):
                    continue
                if st.slots[s] is None:
                    try:
                        grown += len(eng.ensure_slot_pages(s, int(st.pos[s])))
                    except PagePoolOOM:
                        eng.release_slot(s)
                    continue
                while True:
                    try:
                        grown += len(eng.ensure_slot_pages(s, int(st.pos[s])))
                        break
                    except PagePoolOOM:
                        if self._reclaim_retired(st):
                            continue
                        if not self._preempt_for(st, s, on_complete):
                            # cannot happen with a pool >= the configured
                            # minimum (one full slot + growth headroom), but
                            # surface it rather than loop
                            raise
            sp.set_metadata(pages=grown)
        self.stats["pages_grown"] += grown

    def _admit(self, st: _RunState, s: int, on_complete) -> bool:
        """Pull the next request into slot ``s``; returns True if the
        slot ended up active (a request can finish at its very first
        token — then the slot is retired and the next one is tried)."""
        eng = self.engine
        while self.queue:
            r = self.queue.pop(0)
            if self._expired(r, self.clock()):  # dead on arrival at a slot
                self._complete_unadmitted(st, r, s, on_complete, "timeout",
                                          tokens=r.out)
                continue
            if self._budget(st, r) <= 0:  # zero-token request: complete empty
                self._complete_unadmitted(st, r, s, on_complete, "ok")
                continue
            if len(r.prompt) > eng.max_len:
                # no cache layout can hold it — reject with a terminal
                # status instead of silently truncating context
                log.warning("rid=%d prompt len %d exceeds max_len %d; "
                            "rejected", r.rid, len(r.prompt), eng.max_len)
                self.stats["rejected"] += 1
                self._complete_unadmitted(st, r, s, on_complete, "rejected")
                continue
            if self.controller is not None and r.attempts == 0:
                # SLO degradation assigns the admission level; guard-retried
                # requests keep their promoted level instead
                lvl = self.controller.admission_level(len(self.queue))
                if lvl > 0:
                    self.stats["demotions"] += 1
                r.level = lvl
            r.level = min(r.level, eng.n_levels - 1)
            with spans.span(spans.ADMIT, rid=r.rid, slot=s,
                            length=len(r.prompt), level=r.level):
                packed = self._pack(r)
                # last cache write lands at bucket + budget - 2 (the final
                # emitted token is never fed back), so clamping only kicks
                # in beyond max_len + 1
                if len(packed) + self._budget(st, r) > eng.max_len + 1:
                    log.warning(
                        "rid=%d bucket %d + max_new %d exceeds max_len %d; "
                        "late cache writes clamp to the last position",
                        r.rid, len(packed), self._budget(st, r), eng.max_len)
                st.key, sub = jax.random.split(st.key)
                try:
                    first = eng.prefill_slot(s, packed, st.gen, sub,
                                             level=r.level)
                except PagePoolOOM:
                    self._reclaim_retired(st)
                    try:
                        first = eng.prefill_slot(s, packed, st.gen, sub,
                                                 level=r.level)
                    except PagePoolOOM:
                        # queue backpressure: put it back and stop
                        # admitting — decode retires slots, then admission
                        # is retried
                        self.queue.insert(0, r)
                        self.stats["kv_oom"] += 1
                        self.events.append(("kv_oom", r.rid, s, st.step))
                        return False
                self._count_experts("prefill")
                self.stats["prefills"] += 1
                self.stats["prefill_tokens"] += len(packed)
                kind = "refill" if st.step > 0 else "admit"
                self.events.append((kind, r.rid, s, st.step))
                if kind == "refill":
                    self.stats["refills"] += 1
                st.slots[s] = _Slot(req=r, budget=self._budget(st, r),
                                    seq=self._admit_seq)
                self._admit_seq += 1
                st.level[s] = r.level
                r.out.append(first)
                st.slots[s].budget -= 1
                st.tok[s] = first
                st.pos[s] = len(packed)
                st.active[s] = True
                if self.guard_retry:
                    # a violation during THIS batch-1 prefill belongs to slot s
                    self._drain_guard_events(st, on_complete, prefill_slot=s)
                    if st.slots[s] is None:  # re-enqueued (or failed) already
                        continue
                hit_eos = (st.gen.eos_id is not None
                           and first == st.gen.eos_id)
                if st.slots[s].budget <= 0 or hit_eos:
                    # done on the prefill token
                    self._retire(st, s, on_complete)
                    continue
                return True
        return False

    def _drive(self, st: _RunState, on_complete=None,
               max_steps: int | None = None):
        """Advance the scheduler loop from ``st`` until the queue drains (or
        ``max_steps`` decode steps).  ``_on_step_boundary`` fires after each
        completed step — the consistent point where subclasses snapshot."""
        eng = self.engine
        B = eng.batch
        maxpos = eng.max_len - 1
        steps_this_call = 0
        seen = spans.compiles()
        while True:
            step = st.step
            for s in range(B):
                if st.slots[s] is None:
                    self._admit(st, s, on_complete)
            if not st.active.any():
                break
            if max_steps is not None and steps_this_call >= max_steps:
                break  # yield with resumable state (simulated kill point)
            if eng.kv is not None:
                self._grow_pages(st, on_complete)
            t0 = self.clock()
            emitted, st.key = eng.step_slots(st.gen, st.tok, st.pos,
                                             st.active, st.key,
                                             level=st.level)
            self._count_experts("decode")
            if self.controller is not None:
                self.controller.record_step((self.clock() - t0) * 1000.0)
            st.step += 1
            steps_this_call += 1
            self.stats["steps"] += 1
            if self.guard_retry:
                # unrecovered violations tear the slot down BEFORE its
                # (corrupted) token is appended to the request stream
                self._drain_guard_events(st, on_complete)
            n_events = len(self.events)
            with spans.span(spans.RETIRE) as sp:
                for s in range(B):
                    if st.slots[s] is None:
                        continue
                    t = int(emitted[s])
                    st.slots[s].req.out.append(t)
                    st.slots[s].budget -= 1
                    st.tok[s] = t
                    st.pos[s] = min(st.pos[s] + 1, maxpos)
                    hit_eos = (st.gen.eos_id is not None
                               and t == st.gen.eos_id)
                    if st.slots[s].budget <= 0 or hit_eos:
                        self._retire(st, s, on_complete)
                self._expire_slots(st, on_complete)
                sp.set_metadata(retired=len(self.events) - n_events)
            seen = self._count_compiles(seen, step)
            self._count_weights()
            self._on_step_boundary(st)
        self._count_compiles(seen, st.step)
        self._count_weights()
        return st.results

    def _count_compiles(self, seen: int, step: int) -> int:
        """Add the programs compiled since ``seen`` to ``stats`` and name
        the decode step they came with (its admissions, page growth and the
        step itself); returns the count now."""
        now = spans.compiles()
        if now > seen:
            self.stats["compiles"] += now - seen
            log.info("%d program(s) compiled or loaded at decode step %d",
                     now - seen, step)
        return now

    def _count_experts(self, kind: str):
        """Add the engine's last call's expert counts to ``stats`` (token-
        expert pairs routed to the experts it holds, rows they computed)
        and, for a model with experts, to ``expert_log``."""
        rows, slots = self.engine.last_counts
        self.stats["expert_rows"] += rows
        self.stats["expert_slots"] += slots
        if slots:
            self.expert_log.append((time.perf_counter(), kind, rows, slots))

    def _count_weights(self):
        """The engine's weights held as posit words (leaves, bytes), and
        over its traced programs (one per program and ladder level) the
        weight contractions that read stored words and that encode per
        call, and logmac's grid steps and 128x128 sub-tile products; a
        scanned layer's contractions count once."""
        eng = self.engine
        self.stats["weight_leaves"] = eng.word_leaves
        self.stats["weight_bytes"] = eng.word_bytes
        reads = eng.weight_reads.values()
        self.stats["stored_reads"] = sum(r[0] for r in reads)
        self.stats["per_call_reads"] = sum(r[1] for r in reads)
        grid = eng.logmac_grid.values()
        self.stats["logmac_steps"] = sum(g[0] for g in grid)
        self.stats["logmac_tiles"] = sum(g[1] for g in grid)

    def _on_step_boundary(self, st: _RunState):
        """Hook: called after every completed decode step (post-retire).
        ``DurableBatcher`` snapshots here; the base scheduler does nothing."""
