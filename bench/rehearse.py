#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

Compiles, at the cell's sizes, the weight maker, the decode step at the
widest page table, the prefill of the longest prompt and one reference
layer at its largest block, and prints each program's
``memory_analysis()`` bytes.  Nothing runs: a compile that passes here is
not a chip run.  Size a pool from the decode step's line: its arguments
hold the weights and the pool, its outputs a second pool (the step does
not donate the cache).
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, traffic, weights
    from repro.kernels.paged_decode import RESERVED_PAGES
    from repro.numerics.backends import PallasBackend, register_backend

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    register_backend("pallas", PallasBackend(interpret=False))

    mix = dict(cell.mix)
    B, max_len = mix["batch"], mix["max_len"]
    ps = cell.config["serving"]["page_size"]
    n_logical = max_len // ps
    pages = mix.get("num_pages") or B * n_logical + 1 + RESERVED_PAGES
    # a small pool for the host-side engine; the programs get the real one
    mix["num_pages"] = n_logical + 1 + RESERVED_PAGES
    sess = harness.Session(cell.config, mix)
    cfg = sess.model.cfg

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    def report(what, lowered):
        m = lowered.compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{what}: arguments {m.argument_size_in_bytes} outputs "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} total {total}", flush=True)

    key = sds((2,), jnp.uint32)
    build = weights.builder(sess.model, sess.dtype)
    report("weights", jax.jit(build).lower(key))
    params = on_chip(jax.eval_shape(build, jax.random.PRNGKey(0)))
    word = jnp.dtype(cell.config["serving"]["cache_dtype"])
    pool_shape = (cfg.n_layers, pages, ps, cfg.n_kv_heads, cfg.head_dim)
    pool = {"k": sds(pool_shape, word), "v": sds(pool_shape, word)}
    n_words = 2 * cfg.n_layers * pages * ps * cfg.n_kv_heads * cfg.head_dim
    print(f"pool: {pages} pages, {n_words * word.itemsize} bytes")
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    scan = sess.eng._decode_scan(sess.gen, 1, 0)
    report(f"decode step (batch {B}, table {n_logical} pages)",
           scan.lower(params, i32(B), i32(B), sds((B,), bool), pool, key,
                      i32(), i32(B, n_logical), sds((B,), bool)))
    T = max(traffic.prefill_lengths(cell.mix))
    tmpl = jax.eval_shape(lambda: sess.model.init_cache(1, T, word))
    report(f"prefill (length {T})",
           sess.eng._prefill_fns[0].lower(params, i32(1, T), on_chip(tmpl)))
    ref = harness.reference(cell.config)
    c = cell.config
    Tr = -(-(max_len + 1) // 128) * 128
    rows = max(1, ref.BLOCK_TOKENS // Tr)
    dims = (c["n_heads"], c["n_kv_heads"], c["head_dim"],
            float(c["rope_theta"]), float(c["norm_eps"]), c["mlp"])
    report(f"reference layer ({rows} x {Tr} tokens)",
           ref._layer.lower(sds((rows, Tr, c["d_model"]), jnp.float32),
                            params["layers"], i32(), dims=dims))
    report(f"reference head ({rows} x {Tr} tokens)",
           ref._head_gap.lower(sds((rows, Tr, c["d_model"]), jnp.float32),
                               params["ln_f"]["g"], params["embed"]["e"],
                               i32(rows, Tr), sds((rows, Tr), bool),
                               vocab=c["vocab"], eps=float(c["norm_eps"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
