"""Serving driver: load (or init) a model and drain batched requests through
the EULER-ADAS continuous-batching scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \\
      --requests 12 --max-new 16 --euler L-21b --eos-id 7 --stream

Fault-tolerant serving knobs:

  --guard            run the datapath through the ``guarded:<backend>`` ABFT
                     wrapper; unrecovered checksum violations re-enqueue the
                     hit request at higher precision (--guard-retry bound)
  --deadline-ms      per-request wall-clock SLO; expired requests retire
                     with status "timeout" instead of holding their slot
  --degrade-ladder   comma-separated posit widths BELOW the primary format
                     (e.g. "16,8" under --width 32 gives P32->P16->P8);
                     under queue pressure new requests are admitted further
                     down the ladder (--slo-queue-hi requests per level)

  --trace-dir DIR    record the drain with the JAX profiler into DIR; the
                     serving path's ``serve.*`` spans (``serving.spans``)
                     lie on the same clock as the device's ops
"""
from __future__ import annotations

import argparse
import contextlib
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro.core.engine import from_variant
from repro.distributed import checkpoint as CK
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build_numerics
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.numerics import NumericsContext, PrecisionPolicy
from repro.serving import (DurableBatcher, GenerationConfig, PagedKVConfig,
                           QueueFullError, RequestBatcher, ServeEngine,
                           SLOConfig)


def main(argv=None):
    """Serve ``--requests`` random prompts; returns ``{"results": {rid:
    tokens}, "stats": batcher stats, "statuses": {rid: status}, "seconds":
    wall time of the drain, "engine": the ServeEngine}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default); --no-smoke serves FULL")
    ap.add_argument("--euler", default="L-21b")
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--policy", default="",
                    help="PrecisionPolicy JSON (inline or file path)")
    ap.add_argument("--backend", default="lax_ref",
                    help="numerics backend: lax_ref | pallas | exact")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a request at this token id (-1: no EOS)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission cap: submit() fails beyond this many "
                         "queued requests (0: unbounded)")
    ap.add_argument("--stream", action="store_true",
                    help="print each request the step it completes")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--snapshot-dir", default="",
                    help="durable serving: snapshot the scheduler state here "
                         "at step boundaries (enables --resume)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="decode steps between scheduler snapshots")
    ap.add_argument("--resume", action="store_true",
                    help="restore the drain from --snapshot-dir instead of "
                         "submitting fresh requests")
    ap.add_argument("--guard", action="store_true",
                    help="ABFT-guard the datapath (guarded:<backend>) and "
                         "re-enqueue requests hit by unrecovered violations")
    ap.add_argument("--guard-retry", type=int, default=2,
                    help="max guard-triggered re-enqueues per request before "
                         "it retires with status 'failed'")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request wall-clock deadline; 0 disables")
    ap.add_argument("--degrade-ladder", default="",
                    help="comma-separated posit widths below the primary "
                         "format (e.g. '16,8'); enables SLO-aware admission "
                         "degradation")
    ap.add_argument("--slo-queue-hi", type=int, default=4,
                    help="queued requests per one-level admission demotion")
    ap.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help="step-latency p99 threshold adding one more "
                         "demotion level; 0 disables")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: shared page pool + per-slot page "
                         "tables instead of per-slot bucketed rows; with "
                         "--backend pallas and an integer --cache-dtype, "
                         "decode runs the fused flash-decode kernel")
    ap.add_argument("--cache-dtype", default="",
                    choices=["", "bfloat16", "float32", "uint8", "uint16",
                             "uint32"],
                    help="KV cache dtype; integer dtypes store posit "
                         "words; default: the config's")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--max-len must be a multiple)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="physical pages in the pool (0: full occupancy "
                         "for every slot + headroom); smaller values "
                         "oversubscribe HBM with OOM backpressure/preempt")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default="",
                    help="record the drain with the JAX profiler here "
                         "(spans serve.*; view with TensorBoard/XProf)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    enable_compile_cache()

    mod = C.get_config(args.arch)
    cfg = mod.SMOKE if args.smoke else mod.FULL
    if args.guard:
        args.backend = f"guarded:{args.backend}"
    nctx = build_numerics(args)
    ecfg = nctx.policy.default
    model = Model(cfg, ecfg, remat=False, numerics=nctx)
    # serving holds the weights in the config's compute dtype (bf16 for the
    # FULL configs): f32 weights of gemma2-2b alone take 10.6 GB of a 16 GB
    # chip.  Cast inside the jit so the f32 init is never materialized.
    params = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(model.compute_dtype), model.init(k)))(
            jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        restored, step, _ = CK.restore(args.ckpt_dir, {"params": params})
        params = jax.tree.map(lambda a, p: a.astype(p.dtype),
                              restored["params"], params)
        print(f"loaded params from step {step}")

    ctx = Ctx(ecfg=ecfg, numerics=nctx)
    levels = None
    if args.degrade_ladder:
        if args.euler == "exact":
            raise SystemExit("--degrade-ladder needs a posit format "
                             "(--euler), not exact")
        widths = [int(w) for w in args.degrade_ladder.split(",") if w]
        if any(w >= ecfg.width for w in widths):
            raise SystemExit(f"--degrade-ladder widths {widths} must sit "
                             f"strictly below the primary width {ecfg.width}")
        levels = [nctx] + [
            NumericsContext(policy=PrecisionPolicy.uniform(
                from_variant(w, args.euler)), backend=args.backend)
            for w in widths]
    paged = (PagedKVConfig(page_size=args.page_size,
                           num_pages=args.num_pages or None)
             if args.paged else None)
    eng = ServeEngine(model, params, ctx, max_len=args.max_len,
                      batch=args.batch, numerics=nctx, levels=levels,
                      paged=paged,
                      cache_dtype=(jnp.dtype(args.cache_dtype)
                                   if args.cache_dtype else None))
    slo = (SLOConfig(queue_hi=args.slo_queue_hi,
                     p99_ms=args.slo_p99_ms or None)
           if levels else None)
    kw = dict(max_queue=args.max_queue or None, slo=slo,
              guard_retry=args.guard_retry if args.guard else 0)
    if args.snapshot_dir:
        batcher = DurableBatcher(eng, prompt_buckets=(32, 128),
                                 ckpt_dir=args.snapshot_dir,
                                 snapshot_every=args.snapshot_every, **kw)
    else:
        batcher = RequestBatcher(eng, prompt_buckets=(32, 128), **kw)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()

    def on_complete(rid, toks):
        if args.stream:
            print(f"  [{time.time() - t0:6.2f}s] req {rid} done "
                  f"({len(toks)} tokens): {toks[:8]}...")

    if args.resume and not args.snapshot_dir:
        raise SystemExit("--resume requires --snapshot-dir")
    if not args.resume:
        dropped = 0
        for i in range(args.requests):
            plen = int(rng.integers(4, 24))
            try:
                batcher.submit(rng.integers(0, cfg.vocab, plen),
                               max_new=args.max_new,
                               deadline_ms=args.deadline_ms or None)
            except QueueFullError:  # admission control: shed, keep serving
                dropped += 1
        if dropped:
            print(f"queue full: dropped {dropped}/{args.requests} requests "
                  f"(max_queue={args.max_queue})")
    gen = GenerationConfig(max_new_tokens=args.max_new,
                           temperature=args.temperature,
                           eos_id=None if args.eos_id < 0 else args.eos_id)
    with (jax.profiler.trace(args.trace_dir) if args.trace_dir
          else contextlib.nullcontext()):
        results = (batcher.resume(on_complete=on_complete) if args.resume
                   else batcher.run(gen, on_complete=on_complete))
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s) under {ecfg.variant}@posit{ecfg.width} "
          f"[{batcher.stats['steps']} steps, {batcher.stats['refills']} "
          f"mid-stream refills, {batcher.stats['prefills']} prefills, "
          f"{batcher.stats['pages_grown']} pages grown, "
          f"{batcher.stats['compiles']} programs compiled]")
    s = batcher.stats
    if args.paged:
        kv = eng.kv
        print(f"  paged: page_size={kv.page_size}, peak "
              f"{kv.peak_pages}/{kv.alloc.num_pages} pages, "
              f"{s['kv_oom']} OOM backpressures, {s['preempts']} preempts, "
              f"{s['rejected']} rejected")
    if s["weight_leaves"]:
        print(f"  weights: {s['weight_leaves']} held as posit words "
              f"({s['weight_bytes']} bytes); {s['stored_reads']} "
              f"contractions read them, {s['per_call_reads']} encode "
              f"per call")
    if s["timeouts"] or s["guard_retries"] or s["demotions"]:
        print(f"  SLO: {s['timeouts']} timeouts, {s['demotions']} admission "
              f"demotions, {s['guard_retries']} guard retries")
    if args.guard:
        from repro.numerics import api as napi
        t = napi.guard_totals(reset=True)
        print(f"  guard: {t['checks']} checks, {t['violations']} violations, "
              f"{t['recovered']} recovered, {t['unrecovered']} unrecovered")
    for rid in sorted(results)[:4]:
        print(f"  req {rid}: {results[rid][:8]}...")
    return {"results": results, "stats": s, "statuses": batcher.statuses,
            "seconds": dt, "engine": eng}


if __name__ == "__main__":
    main()
