"""Serving benchmark: sustained tokens/sec and per-request completion
latency (p50/p99) through the continuous-batching scheduler, across the
``repro.numerics`` backends and posit widths.

SPADE (arXiv:2601.17279) and Nakasato et al. (arXiv:2401.14117) both argue
posit engines win or lose on *sustained-throughput* behavior, not
single-kernel numbers — this is the serving-loop counterpart of
``engine_bench.py``: the same EULER numerics, but measured through slot
admission, masked decode and mid-stream refill.

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke
  PYTHONPATH=src python benchmarks/serve_bench.py --guard \\
      --backends exact,lax_ref --widths 8,16,32 --out BENCH_serving.json

Latency is measured from ``run()`` start to each request's completion
callback (requests are all queued up front, so this is completion time
under a full queue — the continuous-batching number, not a single-request
cold start).  Every cell runs one UNTIMED warm-up drain first, so the
numbers are steady-state serving throughput (jit compilation excluded);
``--guard`` benches each cell and its ``guarded:<backend>`` twin with
timed passes INTERLEAVED A/B (see :func:`bench_backend`) and reports the
ABFT clean-path overhead as the median of per-pass A/B wall ratios — the
paired estimator, robust to host clock drift between passes.  The paper-
bar (<= 10%) applies to the posit datapath (``lax_ref``), whose per-op
codec work amortizes the thin check contractions; the ``exact`` f32
backend is the degenerate baseline — its base matmul is a single fused
XLA op costing next to nothing, so ANY added check looms large relative
to it.  ``--out`` writes the full grid as ``BENCH_serving.json``
(committed snapshot; wall-clock fields vary by machine, the structure and
token counts do not).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import numerics as N
from repro.core.engine import from_variant
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.serving import (GenerationConfig, PagedKVConfig, RequestBatcher,
                           ServeEngine)


def _make_batcher(backend: str, cfg: ModelConfig, *, batch, max_len, width,
                  variant, buckets, seed, paged=None, cache_dtype=None):
    nctx = N.NumericsContext.from_ecfg(from_variant(width, variant),
                                       backend=backend)
    model = Model(cfg, remat=False, numerics=nctx)
    params = model.init(jax.random.PRNGKey(seed))
    eng = ServeEngine(model, params, max_len=max_len, batch=batch,
                      numerics=nctx, paged=paged, cache_dtype=cache_dtype)
    return RequestBatcher(eng, prompt_buckets=buckets)


def _drain(batcher, gen, cfg, *, requests, max_new, buckets, seed):
    """Submit the canonical traffic mix and time one full queue drain."""
    rng = np.random.default_rng(seed)
    for _ in range(requests):
        plen = int(rng.integers(4, max(buckets) + 1))
        batcher.submit(rng.integers(0, cfg.vocab, plen), max_new=max_new)
    lat: dict[int, float] = {}
    t0 = time.perf_counter()
    results = batcher.run(gen, on_complete=lambda rid, toks:
                          lat.__setitem__(rid, time.perf_counter() - t0))
    return time.perf_counter() - t0, results, lat


def bench_backend(backend: str, cfg: ModelConfig, *, batch: int,
                  max_len: int, requests: int, max_new: int, width: int = 16,
                  variant: str = "L-21b", buckets=(16, 32), seed: int = 0,
                  repeats: int = 1, paired_with: str | None = None):
    """Serve ``requests`` random prompts; returns a metrics dict.

    Runs one UNTIMED drain with identical traffic to compile every
    scan/prefill program, then ``repeats`` timed steady-state drains and
    reports the median-throughput pass.  ``paired_with`` names a second
    backend benched under the SAME traffic with timed passes interleaved
    A/B/A/B — then a ``(main, paired)`` tuple is returned.  Interleaving is
    how the guard-overhead column is measured: back-to-back cells drift by
    tens of percent on a busy host (clock scaling, cache state), which
    swamps a few-percent ABFT delta; alternating passes cancel the drift.
    """
    names = [backend] + ([paired_with] if paired_with else [])
    kw = dict(batch=batch, max_len=max_len, width=width, variant=variant,
              buckets=buckets, seed=seed)
    dkw = dict(requests=requests, max_new=max_new, buckets=buckets, seed=seed)
    gen = GenerationConfig(max_new_tokens=max_new)
    batchers = [_make_batcher(n, cfg, **kw) for n in names]
    for b in batchers:  # warm-up: compile scans/prefills off the clock
        _drain(b, gen, cfg, **dkw)
    passes: list[list] = [[] for _ in batchers]
    for _ in range(max(1, repeats)):
        for i, b in enumerate(batchers):  # interleaved A/B timed passes
            passes[i].append(_drain(b, gen, cfg, **dkw))
    outs = []
    for name, b, ps in zip(names, batchers, passes):
        walls = [p[0] for p in ps]  # original pass order, for A/B pairing
        ps = sorted(ps, key=lambda p: p[0])
        wall, results, lat = ps[len(ps) // 2]  # median-throughput pass
        toks = sum(len(v) for v in results.values())
        ls = np.asarray(sorted(lat.values()))
        outs.append({
            "backend": name,
            "width": width,
            "requests": len(results),
            "tokens": toks,
            "wall_s": round(wall, 4),
            "pass_walls_s": [round(w_, 4) for w_ in walls],
            "tok_per_s": round(toks / wall, 1),
            "p50_ms": round(float(np.percentile(ls, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(ls, 99)) * 1e3, 1),
            "steps": b.stats["steps"],
            "refills": b.stats["refills"],
        })
    return outs[0] if paired_with is None else (outs[0], outs[1])


# ---------------------------------------------------------------------------
# paged-vs-dense decode benchmark (--paged)
# ---------------------------------------------------------------------------

def _drain_prompts(batcher, gen, prompts, max_new):
    """Time one queue drain of an explicit prompt list."""
    for p in prompts:
        batcher.submit(p, max_new=max_new)
    lat: dict[int, float] = {}
    t0 = time.perf_counter()
    results = batcher.run(gen, on_complete=lambda rid, toks:
                          lat.__setitem__(rid, time.perf_counter() - t0))
    return time.perf_counter() - t0, results, lat


def _mixed_traffic(cfg, *, requests, max_len, page_size, max_new, seed):
    """Half short prompts, half long ones capped at max_len/2 — the
    workload where paging pays: dense charges every slot ``max_len`` of
    HBM and attends over all of it, while the paged table window tracks
    the longest LIVE request (here <= max_len/2)."""
    rng = np.random.default_rng(seed)
    cap = max_len // 2
    prompts = []
    for i in range(requests):
        if i % 2 == 0:
            plen = int(rng.integers(4, 2 * page_size + 1))
        else:
            plen = int(rng.integers(cap // 2, max(cap // 2 + 1,
                                                  cap - max_new + 1)))
        prompts.append(rng.integers(0, cfg.vocab, plen))
    return prompts


def _cache_bytes(eng) -> int:
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(eng.cache)))


def _decode_metrics(name, batcher, ps_sorted, walls):
    wall, results, lat = ps_sorted[len(ps_sorted) // 2]
    toks = sum(len(v) for v in results.values())
    ls = np.asarray(sorted(lat.values()))
    return results, {
        "cache": name, "tokens": toks, "wall_s": round(wall, 4),
        "pass_walls_s": [round(w, 4) for w in walls],
        "tok_per_s": round(toks / wall, 1),
        "p50_ms": round(float(np.percentile(ls, 50)) * 1e3, 1),
        "p99_ms": round(float(np.percentile(ls, 99)) * 1e3, 1),
        "steps": batcher.stats["steps"],
        "refills": batcher.stats["refills"],
    }


def bench_decode(cfg: ModelConfig, *, backend: str, batch: int, max_len: int,
                 page_size: int, num_pages: int | None, requests: int,
                 max_new: int, width: int = 16, variant: str = "L-21b",
                 cache_dtype=None, seed: int = 0, repeats: int = 1) -> dict:
    """A/B: dense bucketed KV rows vs the paged pool, same mixed traffic.

    The dense baseline buckets at every page multiple, so both arms pack
    every prompt identically — which is what makes the emitted tokens
    comparable bit-for-bit (recorded as ``parity``).  Timed passes are
    interleaved dense/paged per repeat (same drift-cancelling estimator as
    the guard benchmark).  HBM per slot: dense is the allocation
    (``cache bytes / batch`` — every slot owns a full ``max_len`` row);
    paged is what the pool actually needed at peak
    (``peak_pages * page_bytes / batch``) — the provisioning floor a
    right-sized pool can run at, which dense can never go below.
    """
    buckets = tuple(range(page_size, max_len, page_size))
    prompts = _mixed_traffic(cfg, requests=requests, max_len=max_len,
                             page_size=page_size, max_new=max_new, seed=seed)
    gen = GenerationConfig(max_new_tokens=max_new)
    kw = dict(batch=batch, max_len=max_len, width=width, variant=variant,
              buckets=buckets, seed=seed, cache_dtype=cache_dtype)
    dense = _make_batcher(backend, cfg, **kw)
    paged = _make_batcher(backend, cfg, paged=PagedKVConfig(
        page_size=page_size, num_pages=num_pages), **kw)
    for b in (dense, paged):  # warm-up: compile off the clock
        _drain_prompts(b, gen, prompts, max_new)
    passes = {id(dense): [], id(paged): []}
    for _ in range(max(1, repeats)):
        for b in (dense, paged):  # interleaved A/B timed passes
            passes[id(b)].append(_drain_prompts(b, gen, prompts, max_new))
    out = {}
    res = {}
    for name, b in (("dense", dense), ("paged", paged)):
        ps = passes[id(b)]
        walls = [p[0] for p in ps]
        res[name], out[name] = _decode_metrics(
            name, b, sorted(ps, key=lambda p: p[0]), walls)
    kv = paged.engine.kv
    pool_pages = kv.alloc.num_pages
    page_bytes = _cache_bytes(paged.engine) // pool_pages
    out["dense"]["hbm_per_slot_bytes"] = _cache_bytes(dense.engine) // batch
    out["paged"].update({
        "hbm_per_slot_bytes": kv.peak_pages * page_bytes // batch,
        "peak_pages": kv.peak_pages,
        "pool_pages": pool_pages,
        "page_occupancy": round(kv.peak_pages / pool_pages, 3),
        "kv_oom": paged.stats["kv_oom"],
        "preempts": paged.stats["preempts"],
    })
    # each timed pass re-submits the same prompts, so rids keep counting up
    # across passes; normalize to per-pass submission order before comparing
    # (the two arms may report different median passes)
    def _by_order(res):
        return {r - min(res): toks for r, toks in res.items()}

    nd, np_ = _by_order(res["dense"]), _by_order(res["paged"])
    parity = (sorted(nd) == sorted(np_) and all(
        np.array_equal(nd[r], np_[r]) for r in nd))
    return {
        "kind": "paged_decode", "backend": backend, "width": width,
        "cache_dtype": str(np.dtype(cache_dtype).name) if cache_dtype
                       else "bf16",
        "batch": batch, "max_len": max_len, "page_size": page_size,
        "requests": requests, "max_new": max_new, "seed": seed,
        "repeats": repeats, "model": cfg.name,
        "dense": out["dense"], "paged": out["paged"],
        "parity": bool(parity),
        "speedup": round(out["paged"]["tok_per_s"]
                         / out["dense"]["tok_per_s"], 3),
        "hbm_ratio": round(out["paged"]["hbm_per_slot_bytes"]
                           / out["dense"]["hbm_per_slot_bytes"], 3),
    }


def _append_entry(path: str, entry: dict):
    """Append-style committed record: BENCH_decode.json accumulates one
    entry per run instead of overwriting history."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        doc = {"entries": []}
    doc.setdefault("entries", []).append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", default="exact,lax_ref",
                    help="comma list from: " + ",".join(N.available_backends())
                         + " (pallas runs in interpret mode on the CPU: slow)")
    ap.add_argument("--widths", default="16",
                    help="comma list of posit widths (precision column)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8,
                    help="slots; decode matmuls have batch rows, so small "
                         "batches understate how well per-op work (codec "
                         "AND guard checks) amortizes")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed drains per cell; the median-throughput "
                         "pass is reported (smoke forces 1)")
    ap.add_argument("--guard", action="store_true",
                    help="re-run each cell through guarded:<backend> (lean "
                         "serving profile) and report ABFT clean-path "
                         "overhead vs the unguarded tok/s")
    ap.add_argument("--out", default="",
                    help="write the grid as JSON (BENCH_serving.json); with "
                         "--paged, APPEND an entry (BENCH_decode.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config: exercises admission, masked "
                         "decode and mid-stream refill end-to-end")
    ap.add_argument("--paged", action="store_true",
                    help="bench the paged KV cache A/B against the dense "
                         "bucketed baseline (mixed short/long traffic) "
                         "instead of the backend grid")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page for --paged")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool pages for --paged (0: full-occupancy default)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.batch, args.max_new = 6, 2, 8
        args.repeats = 1
        if args.paged:
            args.max_len, args.page_size = 64, 8
    elif args.paged and args.max_len == 64:
        # mixed short/long traffic needs headroom for "long" to mean
        # something; the committed BENCH_decode entry uses this shape
        args.max_len, args.batch = 256, 4

    if args.smoke:
        cfg = ModelConfig(name="serve-bench", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab=128, loss_chunk=32, q_chunk=32, kv_chunk=32)
    else:
        # big enough that per-op work dominates dispatch overhead — the
        # regime where the guard's thin check contractions amortize (<10%)
        cfg = ModelConfig(name="serve-bench", family="dense", n_layers=2,
                          d_model=192, n_heads=4, n_kv_heads=2, d_ff=384,
                          vocab=256, loss_chunk=32, q_chunk=32, kv_chunk=32)
    widths = [int(w) for w in args.widths.split(",") if w]
    if args.paged:
        backend = args.backends.split(",")[0].strip()
        entry = bench_decode(
            cfg, backend=backend, batch=args.batch, max_len=args.max_len,
            page_size=args.page_size, num_pages=args.num_pages or None,
            requests=args.requests, max_new=args.max_new, width=widths[0],
            seed=args.seed, repeats=args.repeats)
        d, p = entry["dense"], entry["paged"]
        print(f"# paged decode A/B backend={backend} width={widths[0]} "
              f"batch={args.batch} max_len={args.max_len} "
              f"page_size={args.page_size}")
        print("cache,tokens,tok_per_s,p50_ms,p99_ms,steps,refills,"
              "hbm_per_slot_bytes")
        for name, r in (("dense", d), ("paged", p)):
            print(f"{name},{r['tokens']},{r['tok_per_s']:.1f},"
                  f"{r['p50_ms']:.0f},{r['p99_ms']:.0f},{r['steps']},"
                  f"{r['refills']},{r['hbm_per_slot_bytes']}")
        print(f"parity={entry['parity']} speedup={entry['speedup']:.3f} "
              f"hbm_ratio={entry['hbm_ratio']:.3f} "
              f"peak_pages={p['peak_pages']}/{p['pool_pages']} "
              f"(occupancy {p['page_occupancy']:.3f})")
        assert entry["parity"], "paged tokens diverged from dense"
        assert p["hbm_per_slot_bytes"] < d["hbm_per_slot_bytes"], entry
        if args.smoke:
            assert d["tokens"] == args.requests * args.max_new, entry
            assert d["refills"] >= 1, "no mid-stream refill exercised"
        if args.out:
            _append_entry(args.out, entry)
            print(f"appended to {args.out}")
        if args.smoke:
            print("serve_bench paged smoke OK")
        return
    if args.guard:
        # the serving guard profile: event-gated recording, no sentinel
        # encode, and the fast raw-operand check (quant_eps-widened
        # tolerance) — the clean path pays a row-sum and two thin
        # contractions, no extra codec passes
        from repro.numerics.backends import guarded
        from repro.reliability.guards import GuardConfig
        gcfg = GuardConfig(record="events", sentinels=False, max_retries=2,
                           quantize_check=False)
    print(f"# serve_bench batch={args.batch} requests={args.requests} "
          f"max_new={args.max_new} (L-21b @ widths {widths})")
    print("backend,width,requests,tokens,tok_per_s,p50_ms,p99_ms,steps,"
          "refills,guard_overhead_pct")
    rows = []
    for backend in [b.strip() for b in args.backends.split(",")]:
        for width in widths:
            kw = dict(batch=args.batch, max_len=args.max_len,
                      requests=args.requests, max_new=args.max_new,
                      width=width, seed=args.seed, repeats=args.repeats)
            over = ""
            if args.guard:
                gb = guarded(backend, gcfg)
                r, g = bench_backend(backend, cfg, paired_with=gb.name, **kw)
                r["guarded"] = {"tok_per_s": g["tok_per_s"],
                                "p50_ms": g["p50_ms"], "p99_ms": g["p99_ms"],
                                "tokens": g["tokens"],
                                "pass_walls_s": g["pass_walls_s"]}
                # median of per-pass A/B wall ratios: each pair ran seconds
                # apart, so host clock drift cancels pair-wise (median of
                # each arm separately can sample different drift epochs)
                ratios = [gw / rw for rw, gw in
                          zip(r["pass_walls_s"], g["pass_walls_s"])]
                r["guard_overhead_pct"] = round(
                    100.0 * (float(np.median(ratios)) - 1.0), 1)
                over = f"{r['guard_overhead_pct']:.1f}"
            else:
                r = bench_backend(backend, cfg, **kw)
            rows.append(r)
            print(f"{r['backend']},{r['width']},{r['requests']},"
                  f"{r['tokens']},{r['tok_per_s']:.1f},{r['p50_ms']:.0f},"
                  f"{r['p99_ms']:.0f},{r['steps']},{r['refills']},{over}")
            if args.smoke:
                assert r["requests"] == args.requests, r
                assert r["tokens"] == args.requests * args.max_new, r
                assert r["refills"] >= 1, "no mid-stream refill exercised"
                if args.guard:
                    assert r["guarded"]["tokens"] == r["tokens"], r

    if args.out:
        out = {"config": {"backends": args.backends, "widths": widths,
                          "requests": args.requests, "batch": args.batch,
                          "max_new": args.max_new, "seed": args.seed,
                          "repeats": args.repeats, "guard": args.guard,
                          "model": cfg.name},
               "rows": rows}
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")
    if args.smoke:
        print("serve_bench smoke OK")


if __name__ == "__main__":
    main()
