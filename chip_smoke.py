#!/usr/bin/env python3
"""Smoke test on one TPU: gemma2-2b FULL served through the fused posit kernels.

    python chip_smoke.py

Everything runs in this one process, which holds the chip.  Phases:

  a. device check: exits 1 unless JAX's default backend is a TPU;
  b. the posit codec, ``logmac`` and ``paged_flash_decode`` kernels against
     their references at gemma2-2b widths;
  c. ``repro.launch.serve.main`` serving gemma2-2b FULL (random weights from
     a seed) on the ``pallas`` backend with paged uint16 posit-word KV pages;
     every request must return ``--max-new`` tokens, every weight
     contraction must read the engine's stored posit words (none encodes
     a weight per call), and the compiled decode program must contain
     ``tpu_custom_call`` (the fused kernels ran);
  d. the last line of stdout is the JSON object
     ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

A failed phase exits non-zero before (d).  Compile and serve seconds, peak
device bytes and tokens/s are smoke figures, not benchmark metrics.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REQUESTS, MAX_NEW, BATCH, MAX_LEN = 8, 16, 4, 512
SERVE_ARGV = ["--arch", "gemma2-2b", "--no-smoke", "--backend", "pallas",
              "--paged", "--cache-dtype", "uint16", "--batch", str(BATCH),
              "--max-len", str(MAX_LEN), "--requests", str(REQUESTS),
              "--max-new", str(MAX_NEW)]
D_MODEL, D_FF, N_HEADS, N_KV, HEAD_DIM = 2304, 9216, 8, 4, 288
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint32))


def check_codec(key):
    """Kernel encode -> decode is bit-equal to ``repro.core.posit`` for every
    P8/P16/P32 format, on a weight-sized and an activation-sized tensor."""
    from repro.core import posit as P
    from repro.kernels import ops
    for shape in ((D_MODEL, D_FF), (BATCH, D_MODEL)):
        key, k1, k2 = jax.random.split(key, 3)
        x = (jax.random.normal(k1, shape, jnp.float32)
             * jnp.exp2(jax.random.randint(k2, shape, -6, 6)).astype(jnp.float32))
        for width in (8, 16, 32):
            for pc in P.BY_WIDTH[width]:
                pat = ops.encode(x, pc)
                want_pat = P.encode_from_float(x, pc)
                check(bool(jnp.array_equal(pat, want_pat)),
                      f"encode {pc} {shape}: patterns differ from core.posit")
                val = ops.decode(pat, pc)
                want_val = P.decode_to_float(want_pat, pc)
                check(np.array_equal(_bits(val), _bits(want_val)),
                      f"decode {pc} {shape}: values differ from core.posit")
                print(f"  codec {pc.name:>10} {shape}: bit-equal")


def check_logmac(key):
    """Fused encode + ``logmac`` on (8, 2304) x (2304, 9216) against
    ``core.engine.euler_dot_general`` (tests/test_kernels.py tolerance)."""
    from repro.core.engine import euler_dot_general, from_variant
    from repro.kernels import ops
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (8, D_MODEL), jnp.float32)
    w = jax.random.normal(k2, (D_MODEL, D_FF), jnp.float32)
    dn = (((1,), (0,)), ((), ()))
    for width in (8, 16, 32):
        cfg = from_variant(width, "L-21b", pre_scale=False)
        got = ops.euler_matmul_fused(x, w, cfg, bm=8, bn=128, bk=128)
        with jax.default_matmul_precision("highest"):
            want = euler_dot_general(x, w, dn, cfg)
        err = float(jnp.max(jnp.abs(got - want) - 1e-4 * jnp.abs(want)))
        check(err <= 1e-3, f"logmac P{width}: off the reference by {err}")
        print(f"  logmac P{width} (8,{D_MODEL})x({D_MODEL},{D_FF}): "
              f"max excess error {err:.3e} <= 1e-3")


def check_paged_decode(key):
    """``paged_flash_decode`` (H 8, KV 4, hd 288, uint16 pages) against
    ``paged_attention_reference`` (tests/test_kvcache.py tolerance)."""
    from repro.core import posit as P
    from repro.core.engine import from_variant
    from repro.kernels.paged_decode import (NULL_PAGE, RESERVED_PAGES,
                                            TRASH_PAGE,
                                            paged_attention_reference,
                                            paged_flash_decode)
    cfg = from_variant(16, "L-21b")
    pc = P.storage_pc(jnp.uint16, cfg.posit)
    ps = 16
    nlp = MAX_LEN // ps
    num_pages = RESERVED_PAGES + BATCH * nlp
    k1, k2, k3, k4 = jax.random.split(key, 4)
    shape = (num_pages, ps, N_KV, HEAD_DIM)
    reserved = jnp.arange(num_pages)[:, None, None, None] < RESERVED_PAGES
    kf = jnp.where(reserved, 0.0, jax.random.normal(k1, shape, jnp.float32))
    vf = jnp.where(reserved, 0.0, jax.random.normal(k2, shape, jnp.float32))
    k_pages = P.to_storage(P.encode_from_float(kf, pc), pc)
    v_pages = P.to_storage(P.encode_from_float(vf, pc), pc)
    pos = jax.random.randint(k3, (BATCH,), 0, MAX_LEN)
    # slot b owns pages RESERVED + b*nlp ...; pages past pos stay NULL
    owned = RESERVED_PAGES + jnp.arange(BATCH)[:, None] * nlp + jnp.arange(nlp)
    table = jnp.where(jnp.arange(nlp)[None, :] <= (pos // ps)[:, None],
                      owned, NULL_PAGE).astype(jnp.int32)
    check(not bool(jnp.any(table == TRASH_PAGE)), "trash page in a table")
    q = jax.random.normal(k4, (BATCH, 1, N_HEADS, HEAD_DIM), jnp.float32)
    for window in (None, 64):
        out = paged_flash_decode(q, k_pages, v_pages, table, pos, window,
                                 pc=pc, cfg_qk=cfg, cfg_pv=cfg, softcap=50.0,
                                 interpret=False)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_reference(q, k_pages, v_pages, table, pos,
                                             pc=pc, softcap=50.0,
                                             window=window)
        check(out.shape == want.shape,
              f"paged decode shape {out.shape} != {want.shape}")
        diff = float(jnp.max(jnp.abs(out - want)))
        check(diff < 0.05 and float(jnp.max(jnp.abs(out))) > 0.0,
              f"paged decode window={window}: max |diff| {diff}")
        print(f"  paged_flash_decode uint16 H{N_HEADS} KV{N_KV} hd{HEAD_DIM} "
              f"window={window}: max |diff| {diff:.3e} < 0.05")


def serve(argv, max_new: int, requests: int):
    """Phase (c): drain through the serving entry point, then prove the
    decode program holds the fused kernels.  Returns the figures printed."""
    from repro.launch import serve as serve_mod
    from repro.serving import GenerationConfig
    compiles = []  # (end time, seconds) of each XLA compile

    def on_duration(event, seconds, **_):
        if event == COMPILE_EVENT:
            compiles.append((time.perf_counter(), seconds))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    out = serve_mod.main(argv)
    drain_start = time.perf_counter() - out["seconds"]
    compile_all = sum(s for _, s in compiles)
    compile_in_drain = sum(s for t, s in compiles if t >= drain_start)
    results, statuses = out["results"], out["statuses"]
    check(len(results) == requests,
          f"served {len(results)} of {requests} requests")
    for rid, toks in results.items():
        check(statuses[rid] == "ok" and len(toks) == max_new,
              f"request {rid}: status {statuses[rid]}, {len(toks)} tokens "
              f"(want {max_new})")

    s = out["stats"]
    check(s["weight_leaves"] > 0 and s["stored_reads"] > 0
          and s["per_call_reads"] == 0,
          f"weights held {s['weight_leaves']}, read as words "
          f"{s['stored_reads']}, encoded per call {s['per_call_reads']}")
    eng = out["engine"]
    B = eng.batch
    scan = eng._decode_scan(GenerationConfig(max_new_tokens=max_new), 1, 0)
    table = eng.kv.table_device()[:, :eng._table_cap()]
    compiled = scan.lower(eng.served, jnp.zeros(B, jnp.int32),
                          jnp.zeros(B, jnp.int32), jnp.zeros(B, bool),
                          eng.cache, jax.random.PRNGKey(0), jnp.int32(0),
                          table, jnp.ones(B, bool)).compile()
    n_custom = compiled.as_text().count("tpu_custom_call")
    check(n_custom > 0, "decode program holds no tpu_custom_call")
    tokens = sum(len(t) for t in results.values())
    return {"compile_s": compile_all,
            "serve_s": out["seconds"] - compile_in_drain,
            "tokens": tokens, "tpu_custom_calls": n_custom,
            "decode_memory": compiled.memory_analysis()}


def main() -> int:
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[a] device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"; compile cache {cache_dir}")

    key = jax.random.PRNGKey(0)
    k_codec, k_mac, k_dec = jax.random.split(key, 3)
    print("[b] kernels against their references")
    check_codec(k_codec)
    check_logmac(k_mac)
    check_paged_decode(k_dec)

    print(f"[c] serve: {' '.join(SERVE_ARGV)}")
    t0 = time.perf_counter()
    fig = serve(SERVE_ARGV, MAX_NEW, REQUESTS)
    print(f"  {REQUESTS} requests x {MAX_NEW} tokens served; decode program "
          f"holds {fig['tpu_custom_calls']} tpu_custom_call ops "
          f"({time.perf_counter() - t0:.1f}s for the phase)")
    print(f"compile seconds (XLA compiles in the serve phase): "
          f"{fig['compile_s']}")
    print(f"serve seconds (drain wall minus its compiles): {fig['serve_s']}")
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    mem = fig["decode_memory"]
    print(f"decode program bytes: arguments {mem.argument_size_in_bytes}, "
          f"outputs {mem.output_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}")
    print(f"tokens/s (smoke figure, not a metric): "
          f"{fig['tokens'] / fig['serve_s']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
