"""Engine micro-benchmarks on this host (CPU): relative cost of the EULER
modes vs exact matmul across numerics backends.  Wall times are CPU-only
(TPU is the target); the RATIOS between modes are the informative signal
(the euler two-plane path should cost ~2x exact).

Every matmul routes through ``repro.numerics`` — the same dispatch models
and serving use — so a backend shootout is one flag:

  PYTHONPATH=src python benchmarks/engine_bench.py --backend lax_ref
  PYTHONPATH=src python benchmarks/engine_bench.py --backend pallas --size 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import numerics as N
from repro.core.engine import EXACT, EulerConfig, from_variant


def _time(fn, *args, iters=10):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us


MODES = [
    ("exact", EXACT),
    ("posit16_exact", EulerConfig(width=16, mode="posit")),
    ("euler16_L-21b", from_variant(16, "L-21b")),
    ("euler8_L-21b", from_variant(8, "L-21b")),
    ("euler32_L-21b", from_variant(32, "L-21b")),
    ("quant_only16", EulerConfig(width=16, mode="quant_only")),
]


def run(m=512, k=512, n=512, backend="lax_ref", iters=10):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    rows = []
    for name, cfg in MODES:
        nctx = N.NumericsContext.from_ecfg(cfg, backend=backend)
        f = jax.jit(lambda x, y, c=nctx: N.matmul(x, y, c))
        us = _time(f, a, b, iters=iters)
        rows.append((name, us))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="lax_ref",
                    choices=N.available_backends(),
                    help="numerics backend to benchmark")
    ap.add_argument("--size", type=int, default=512,
                    help="square matmul dimension (keep small for pallas "
                         "interpret mode on the CPU)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    rows = run(args.size, args.size, args.size, backend=args.backend,
               iters=args.iters)
    base = rows[0][1]
    print(f"# backend={args.backend} size={args.size}")
    print("mode,us_per_call,ratio_vs_exact")
    for name, us in rows:
        print(f"{name},{us:.1f},{us / base:.2f}")


if __name__ == "__main__":
    main()
