"""Benchmark orchestrator — one section per paper table.

  python -m benchmarks.run              # all sections
  python -m benchmarks.run table1 hw    # a subset
"""
from __future__ import annotations

import sys
import time


SECTIONS = ("table1", "hw", "accuracy", "prototype", "engine",
            "reliability", "decode")


def _section(name):
    print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
    t0 = time.time()
    if name == "table1":
        from benchmarks import table1_error
        table1_error.main()
    elif name == "hw":
        from benchmarks import table_hw
        table_hw.main()
    elif name == "accuracy":
        from benchmarks import table_accuracy
        table_accuracy.main()
    elif name == "prototype":
        from benchmarks import table9_prototype
        table9_prototype.main()
    elif name == "engine":
        from benchmarks import engine_bench
        engine_bench.main([])  # argv isolation: section names are not flags
    elif name == "decode":
        # paged-vs-dense decode A/B at the committed BENCH_decode.json
        # shape; --out appends an entry (history accumulates, not replaced)
        from benchmarks import serve_bench
        serve_bench.main(["--paged", "--backends", "pallas",
                          "--widths", "16", "--requests", "12",
                          "--max-new", "16", "--repeats", "2",
                          "--out", "BENCH_decode.json"])
    elif name == "reliability":
        from repro.core import reliability as R
        from repro.core import posit as P
        print("width,R,eta,gamma_vs_std")
        for width in (8, 16):
            etas = R.ece_vs_regime_bound(width, (2, 3, 5))
            std = R.ece(P.BY_WIDTH[width][0])["eta"]
            for r, eta in etas.items():
                print(f"{width},{r},{eta:.4f},{std / eta:.3f}")
    print(f"-- {name} done in {time.time() - t0:.1f}s")


def main() -> None:
    wanted = sys.argv[1:] or list(SECTIONS)
    for name in wanted:
        if name not in SECTIONS:
            raise SystemExit(f"unknown section {name}; known: {SECTIONS}")
        _section(name)


if __name__ == '__main__':
    main()
