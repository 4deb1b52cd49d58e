#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``check``: each number the output check compares, with its limit.  The
same numbers close standard error.  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    cell = harness.load_cell(args.workload, bool(args.trace))
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"bench: needs a TPU, but JAX's default backend is "
              f"{platform!r}", file=sys.stderr)
        return 3
    if len(jax.devices()) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 3
    compiles = harness.Compiles()
    # inside the checkout, at a fixed path, whatever the environment says:
    # two checkouts measured side by side never share compiled programs
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                     T_START, compiles)
    return 0


if __name__ == "__main__":
    sys.exit(main())
