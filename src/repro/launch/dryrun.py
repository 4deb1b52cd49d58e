import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step, in_shardings=..., out_shardings=...).lower(*specs).compile()``
must succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh for
every assigned architecture and input shape.  The compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (does it fit 16 GB HBM)
  * ``cost_analysis()``    — per-device HLO FLOPs / bytes accessed
  * ``as_text()``          — post-SPMD optimized HLO, parsed for every
    all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute (op, dtype, per-device bytes, group size)

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --out artifacts/dryrun
  python -m repro.launch.dryrun --all --jobs 6        # parallel worker procs
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro.configs import euler_nce
from repro.distributed import sharding as SH
from repro.launch.mesh import HW, make_production_mesh
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.optim import AdamW, cosine_schedule
from repro.training import TrainState, init_state, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?P<shapes>[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")


def _split_computations(hlo_text: str):
    """computation name -> list of instruction lines (text-level HLO parse)."""
    comps, cur, name, entry = {}, None, None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and "{" in line:
            m = _COMP_RE.match(line.strip())
            if m:
                name = m.group(1)
                cur = comps.setdefault(name, [])
                if line.lstrip().startswith("ENTRY"):
                    entry = name
            continue
        if line.startswith("}"):
            name, cur = None, None
            continue
        if cur is not None:
            cur.append(line)
    return comps, entry


def _comp_multipliers(comps: dict, entry: str, scope_trips: dict):
    """Execution multiplier per computation, propagated through the call
    graph: a while body executes caller_mult x trip(while); fusions/calls
    execute caller_mult.  trip(while) comes from the INNERMOST named scan
    scope on the while's own op_name (jax.named_scope set by the model)."""
    mult = {entry: 1.0} if entry else {}
    # edges: caller -> (callee, factor)
    edges: dict[str, list] = {}
    for cname, lines in comps.items():
        for line in lines:
            factor = 1.0
            if " while(" in line:
                nm = _OPNAME_RE.search(line)
                path = nm.group(1) if nm else ""
                # innermost scope present in the path
                best = None
                for scope in scope_trips:
                    idx = path.rfind(f"/{scope}/")
                    if idx < 0 and path.startswith(f"{scope}/"):
                        idx = 0
                    if idx >= 0 and (best is None or idx > best[0]):
                        best = (idx, scope)
                if best:
                    factor = float(scope_trips[best[1]])
                for m in (_BODY_RE.search(line), _COND_RE.search(line)):
                    if m:
                        edges.setdefault(cname, []).append((m.group(1), factor))
            else:
                for callee in _CALL_RE.findall(line):
                    edges.setdefault(cname, []).append((callee, 1.0))
    # propagate (call graph is a DAG; iterate to fixpoint for safety)
    for _ in range(64):
        changed = False
        for caller, outs in edges.items():
            cm = mult.get(caller)
            if cm is None:
                continue
            for callee, f in outs:
                nv = cm * f
                if mult.get(callee, 0) < nv:
                    mult[callee] = nv
                    changed = True
        if not changed:
            break
    return mult


def parse_collectives(hlo_text: str, scope_trips: dict | None = None):
    """Sum per-device result bytes of every collective in optimized HLO.

    XLA reports a while (lax.scan) body once, so each collective's bytes are
    multiplied by the trip counts of the loops that PHYSICALLY contain it —
    derived from the computation call graph (a hoisted loop-invariant
    all-gather keeps its scan-scope op_name but sits outside the body, so
    metadata-only attribution would overcount it by the trip count)."""
    scope_trips = scope_trips or {}
    comps, entry = _split_computations(hlo_text)
    mult = _comp_multipliers(comps, entry, scope_trips)
    out = {}
    for cname, lines in comps.items():
        cm = mult.get(cname, 1.0)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m or "-done" in line:
                continue
            op = m.group("op")
            bytes_ = 0
            for dt, dims in _SHAPE_RE.findall(m.group("shapes")):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                bytes_ += n * _DTYPE_BYTES[dt]
            g = _GROUP_RE.search(line)
            group = int(g.group(2)) if g else 0
            rec = out.setdefault(op, {"count": 0, "bytes": 0,
                                      "bytes_effective": 0, "max_group": 0})
            rec["count"] += 1
            rec["bytes"] += bytes_
            rec["bytes_effective"] += bytes_ * cm
            rec["max_group"] = max(rec["max_group"], group)
    return out


def _active_param_counts(params, cfg):
    """(total, active) parameter counts; MoE experts scaled by top_k/E."""
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        n = int(np.prod(leaf.shape))
        total += n
        if "moe" in names and "router" not in names and "dense" not in names:
            active += n * cfg.top_k / max(cfg.n_experts, 1)
        else:
            active += n
    return total, int(active)


def build_cell(arch: str, shape: str, mesh, *, ecfg=None, cfg_override=None,
               fsdp_experts=None, ctx_overrides=None, model_kwargs=None,
               grad_accum=None):
    """Construct (fn, abstract args, in_shardings, meta) for one cell."""
    mod = C.get_config(arch)
    cfg = cfg_override or mod.FULL
    spec = C.SHAPES[shape]
    kind = spec["kind"]
    B, T = spec["global_batch"], spec["seq_len"]
    ecfg = ecfg or euler_nce.for_arch(cfg.dtype)
    model = Model(cfg, ecfg, **(model_kwargs or {}))
    key = jax.random.PRNGKey(0)

    fsdp = fsdp_experts
    if fsdp is None:
        fsdp = cfg.family == "moe" and cfg.n_experts >= 64  # arctic fits via ZeRO-3
    ctx = Ctx(ecfg=ecfg, mesh=mesh, moe_fsdp=fsdp, **(ctx_overrides or {}))
    p_abs = jax.eval_shape(model.init, key)
    p_shard = SH.params_shardings(p_abs, mesh, fsdp_experts=fsdp)
    cdt = jnp.dtype(cfg.dtype)

    def tok_spec(b, t):
        if cfg.embedding_inputs:
            return jax.ShapeDtypeStruct((b, t, cfg.d_model), cdt)
        return jax.ShapeDtypeStruct((b, t), jnp.int32)

    total, active = _active_param_counts(p_abs, cfg)
    trips = {"layers": cfg.n_layers}
    if kind == "train":
        trips["loss_chunks"] = T // min(cfg.loss_chunk, T)
    if kind in ("train", "prefill") and cfg.family != "ssm":
        trips["attn_kv"] = T // min(cfg.kv_chunk, T)
    if kind in ("train", "prefill") and cfg.family in ("ssm", "hybrid"):
        trips["ssd_chunks"] = T // min(cfg.ssm_chunk, T)
    meta = {"arch": arch, "shape": shape, "kind": kind, "batch": B, "seq": T,
            "params_total": total, "params_active": active,
            "fsdp_experts": fsdp, "euler_variant": ecfg.variant,
            "scope_trips": trips,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape))}

    if kind == "train":
        # optimizer state dtype: bf16 moments for the biggest MoE (arctic)
        sdt = jnp.bfloat16 if total > 1e11 else jnp.float32
        opt = AdamW(lr=cosine_schedule(3e-4, 2000, 100_000), state_dtype=sdt)
        st_abs = jax.eval_shape(lambda k: init_state(model, opt, k), key)
        o_shard = SH.opt_shardings(p_abs, mesh, fsdp_experts=fsdp)
        st_shard = TrainState(
            params=p_shard,
            opt={"m": o_shard, "v": o_shard, "count": SH.replicated(mesh)},
            step=SH.replicated(mesh), ef=None)
        batch_abs = {"inputs": tok_spec(B, T),
                     "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        b_shard = SH.batch_shardings(mesh, batch_abs)
        # microbatch the 100B+ models: same global batch, 8 sequential
        # microsteps — token-space temporaries shrink 8x (grads are taken
        # per microbatch inside the accumulation scan)
        ga = grad_accum if grad_accum else (8 if total > 1e11 else 1)
        meta["grad_accum"] = ga
        if ga > 1:
            trips["grad_accum"] = ga
        step_fn = make_train_step(model, opt, ctx, grad_accum=ga)
        meta["model_flops"] = 6.0 * active * B * T
        return (step_fn, (st_abs, batch_abs), (st_shard, b_shard),
                (st_shard, None), meta)

    cache_len = T
    def _cache_bytes(tree):
        return int(sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree.leaves(tree)))
    if kind == "prefill":
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(B, cache_len))
        meta["cache_bytes"] = _cache_bytes(cache_abs)
        c_shard = SH.cache_shardings(mesh, cache_abs)
        toks = tok_spec(B, T)
        b_shard = SH.batch_shardings(mesh, {"inputs": toks})["inputs"]
        fn = lambda p, toks, cache: model.prefill(p, toks, ctx, cache)
        meta["model_flops"] = 2.0 * active * B * T
        return (fn, (p_abs, toks, cache_abs), (p_shard, b_shard, c_shard),
                None, meta)

    if kind == "decode":
        cache_abs = jax.eval_shape(lambda: model.init_cache(B, cache_len))
        meta["cache_bytes"] = _cache_bytes(cache_abs)
        c_shard = SH.cache_shardings(mesh, cache_abs)
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        b_shard = SH.batch_shardings(mesh, {"t": tok})["t"]
        fn = lambda p, tok, pos, cache: model.decode_step(p, tok, pos, cache, ctx)
        meta["model_flops"] = 2.0 * active * B
        return (fn, (p_abs, tok, pos, cache_abs),
                (p_shard, b_shard, SH.replicated(mesh), c_shard), None, meta)

    raise ValueError(kind)


def run_cell(arch: str, shape: str, multi_pod: bool, *, ecfg=None,
             cfg_override=None, fsdp_experts=None, ctx_overrides=None,
             model_kwargs=None, grad_accum=None) -> dict:
    """Lower + compile one cell; return the roofline artifact record."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    fn, args, in_sh, out_sh, meta = build_cell(
        arch, shape, mesh, ecfg=ecfg, cfg_override=cfg_override,
        fsdp_experts=fsdp_experts, ctx_overrides=ctx_overrides,
        model_kwargs=model_kwargs, grad_accum=grad_accum)
    rec = dict(meta)
    rec.update({"multi_pod": multi_pod, "n_devices": n_dev, "ok": False})
    try:
        with mesh:
            # train: donate the state so params/opt buffers alias in-place
            jitted = (jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(0,))
                      if out_sh is not None else
                      jax.jit(fn, in_shardings=in_sh))
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            # trip-aware analytic FLOPs/traffic from the (global) jaxpr
            from repro.analysis import costmodel
            an = costmodel.analyze(fn, *args)
        colls = parse_collectives(hlo, meta.get("scope_trips"))
        rec.update({
            "ok": True,
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "per_device_total": (ma.argument_size_in_bytes
                                     + ma.output_size_in_bytes
                                     + ma.temp_size_in_bytes
                                     - ma.alias_size_in_bytes),
                "hbm_capacity": HW["hbm_bytes"],
            },
            "cost": {"flops_per_device": ca.get("flops", 0.0),
                     "bytes_per_device": ca.get("bytes accessed", 0.0)},
            "analytic": {
                "dot_flops_global": an["dot_flops"],
                "ew_flops_global": an["ew_flops"],
                "dot_traffic_global": an["dot_traffic"],
                "flops_per_device": (an["dot_flops"] + an["ew_flops"]) / n_dev,
                "dot_traffic_per_device": an["dot_traffic"] / n_dev,
            },
            "collectives": colls,
        })
        fits = rec["memory"]["per_device_total"] <= HW["hbm_bytes"]
        rec["fits_hbm"] = bool(fits)
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    return rec


def _print_summary(rec):
    m = rec.get("memory", {})
    a = rec.get("analytic", {})
    coll_b = sum(v.get("bytes_effective", v.get("bytes", 0))
                 for v in rec.get("collectives", {}).values())
    status = "OK " if rec.get("ok") else "FAIL"
    print(f"[{status}] {rec['arch']:24s} {rec['shape']:12s} "
          f"mesh={'2x16x16' if rec['multi_pod'] else '16x16':8s} "
          f"mem/dev={m.get('per_device_total', 0)/2**30:7.2f}GiB "
          f"fits={rec.get('fits_hbm', '-')} "
          f"gflops/dev={a.get('flops_per_device', 0)/1e9:10.1f} "
          f"coll/dev={coll_b/2**20:9.1f}MiB "
          f"compile={rec.get('compile_s', 0):6.1f}s")
    if not rec.get("ok"):
        print("      ", rec.get("error", "?")[:500])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--jobs", type=int, default=1,
                    help="parallel worker processes for --all")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.all:
        cells = [(a, s, mp) for a, s, app in C.all_cells() if app
                 for mp in meshes]
        if args.jobs > 1:
            procs, pending = [], list(cells)
            while pending or procs:
                while pending and len(procs) < args.jobs:
                    a, s, mp = pending.pop(0)
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", a, "--shape", s,
                           "--mesh", "multi" if mp else "single",
                           "--out", args.out]
                    procs.append(((a, s, mp), subprocess.Popen(cmd)))
                done = [(k, p) for k, p in procs if p.poll() is not None]
                procs = [(k, p) for k, p in procs if p.poll() is None]
                for (a, s, mp), p in done:
                    if p.returncode != 0:
                        print(f"[worker FAIL rc={p.returncode}] {a} {s} mp={mp}")
                time.sleep(1.0)
            return
        rc = 0
        for a, s, mp in cells:
            rec = run_cell(a, s, mp)
            _print_summary(rec)
            fn = f"{args.out}/{a}__{s}__{'multi' if mp else 'single'}.json"
            with open(fn, "w") as f:
                json.dump(rec, f, indent=1)
            rc |= 0 if rec["ok"] else 1
        sys.exit(rc)

    assert args.arch and args.shape
    rc = 0
    for mp in meshes:
        rec = run_cell(args.arch, args.shape, mp)
        _print_summary(rec)
        fn = (f"{args.out}/{args.arch}__{args.shape}__"
              f"{'multi' if mp else 'single'}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
        rc |= 0 if rec["ok"] else 1
    sys.exit(rc)


if __name__ == "__main__":
    main()
