#!/usr/bin/env python3
"""Read the output check's number on many seeds in one process: the
program as configured (the lower reading), the control, the same serving
path under the configuration's ``control_posit_width`` policy (the upper
reading), and the program with a fault of ``bench/faults.py`` planted.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13 --control-seeds 21,22,23 \\
        --faults stale_state,half_batch --fault-seeds 31

Each seed runs the cell's closed loop for ``--seconds`` at the cell's own
load on one engine per precision or fault (set-up compiles once each),
then the check against the plain reference.  Prints one JSON line per
seed; the limit in ``bench/limits/<cell>.json`` is set from these
readings.
"""
import gc
import sys
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, width, seeds, seconds, out, fault=None):
    from bench import faults, harness, traffic
    sess = harness.Session(cell.config, cell.mix, width,
                           patch=faults.ALL[fault] if fault else None)
    warm = False
    for seed in seeds:
        t0 = time.perf_counter()
        sess.load_weights(seed)
        if sess.eng.cache is None:
            sess.eng.cache = sess.model.init_paged_cache(
                sess.eng.kv.alloc.num_pages, sess.eng.kv.page_size,
                sess.eng._cache_dtype)
        if not warm:
            sess.warm_up()
            warm = True
        stream = traffic.Stream(cell.mix, seed, cell.config["vocab"])
        rec = sess.serve(stream, seconds)
        sess.drop_cache()
        sample = harness.check_sample(rec, cell.mix, seed)
        gap = harness.logit_gap(sess.params, cell.config, sample)
        line = {"workload": cell.name, "posit_width": width, "fault": fault,
                "seed": seed,
                "logit_gap": gap, "requests": len(sample),
                "tokens": sum(len(r.tokens) for r in sample),
                "window_s": rec.w1 - rec.w0,
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), file=out, flush=True)
        print(json.dumps(line), flush=True)
    del sess
    gc.collect()  # the session's wrappers hold it in cycles: free its memory


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="chiprun_out/calibrate.jsonl")
    args = ap.parse_args(argv)
    import jax
    from bench import harness
    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    cell = harness.load_cell(args.workload, False)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as out:
        sv = cell.config["serving"]
        if control:
            readings(cell, sv["control_posit_width"], control, args.seconds,
                     out)
        for fault in filter(None, args.faults.split(",")):
            readings(cell, sv["posit_width"], fault_seeds, args.seconds, out,
                     fault)
        if seeds:
            readings(cell, sv["posit_width"], seeds, args.seconds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
