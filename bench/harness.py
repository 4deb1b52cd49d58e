"""Drive one benchmark cell through the program's serving path.

The entry the window drives is the one ``repro.launch.serve`` builds:
``Model`` -> ``ServeEngine`` (pallas backend, one posit policy, paged
posit-word KV pages) -> ``RequestBatcher``.  The scheduler is advanced one
decode step at a time through its resumable loop (``_begin`` once, then
``_drive(max_steps=1)``), and a closed loop of clients submits from
``on_complete``.  Time stamps come from wrappers the benchmark puts around
the engine's ``prefill_slot`` (first token: it ends in a host sync),
``step_slots`` (one token per active slot: it ends in a host sync) and
``ensure_slot_pages`` (page growth); the program itself is not changed.

Set-up makes the weights, warms every prefill length and page-table width
the mix can reach, and fills the slots; the window then compiles nothing
(compiles are counted by a ``jax.monitoring`` listener).  After the
window, the program's KV pool is dropped and the served tokens are checked
against the plain reference (``bench/references``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# What BENCHMARK.json and the data files say
# ---------------------------------------------------------------------------

def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict | None
    metrics: list          # metric entries of BENCHMARK.json this run prints


# configuration keys that describe it without changing what runs
CONFIG_NOTES = frozenset({"name", "source", "reference", "serving",
                          "published", "deployment", "assumed"})
SERVING_KEYS = frozenset({"backend", "posit_width", "variant",
                          "control_posit_width", "cache_dtype", "page_size"})
# ModelConfig carries these keys, but the program's Model reads neither: it
# always ties the head to the embedding, and its RMSNorm has a fixed eps
PROGRAM_FIXED = {"tie_embeddings": True, "norm_eps": 1e-6}


def check_data(config: dict, mix: dict) -> None:
    """Refuse a configuration or mix with a key, or a value, that the
    harness, the program or the reference would silently not run."""
    from bench import traffic
    ref = reference(config)
    bad = sorted(set(config) - CONFIG_NOTES - ref.KEYS)
    bad += [f"serving.{k}" for k in
            sorted(set(config["serving"]) - SERVING_KEYS)]
    bad += [f"{k}={config[k]!r} (the program runs {v!r} only)"
            for k, v in PROGRAM_FIXED.items() if config.get(k, v) != v]
    if config["mlp"] not in ref.MLPS:
        bad.append(f"mlp={config['mlp']!r} (the reference has {ref.MLPS})")
    bad += [f"mix.{k}" for k in sorted(set(mix) - traffic.KEYS)]
    for part in ("prompt", "output"):
        bad += [f"mix.{part}.{k}" for k in
                sorted(set(mix[part]) - traffic.LENGTH_KEYS)]
    if bad:
        raise ValueError(f"not run by this harness: {', '.join(bad)}")


def load_cell(name: str, trace: bool, spec_path: Path | None = None) -> Cell:
    spec = _json(spec_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind]
               if name in m.get("workloads", [name])]
    limits_path = BENCH / "limits" / f"{name}.json"
    config = _json(ROOT / conf["file"])
    mix = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    check_data(config, mix)
    return Cell(name=name, chips=w["chips"], config=config, mix=mix,
                limits=_json(limits_path) if limits_path.exists() else None,
                metrics=metrics)


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(config: dict):
    path = BENCH / "references" / f"{config['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{config['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peak_of(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise RuntimeError(f"no peak figures for device kind "
                           f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Compile counter
# ---------------------------------------------------------------------------

class Collections:
    """Pauses of Python's cyclic garbage collector, by their end time."""

    def __init__(self):
        self.at: list[tuple[float, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def close(self):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            t = time.perf_counter()
            self.at.append((t, t - self._t0))

    def between(self, a: float, b: float) -> tuple[int, float]:
        inside = [d for t, d in self.at if a <= t <= b]
        return len(inside), sum(inside)


class Compiles:
    """Times of every XLA program compile in this process, a load from the
    persistent compilation cache included (JAX times both as one event)."""

    def __init__(self):
        import jax
        self.at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.at.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.at)


# ---------------------------------------------------------------------------
# One run's record
# ---------------------------------------------------------------------------

class Step(NamedTuple):
    t0: float
    t1: float
    rows: int          # active slots
    ctx_tokens: int    # their context lengths, summed (this token included)
    live_pages: int    # pool pages mapped before the step
    pool_pages: int


class Prefill(NamedTuple):
    t0: float
    t1: float
    length: int
    rid: int


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    submit: float
    prefill_start: float | None = None
    first: float | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Record:
    reqs: dict = dataclasses.field(default_factory=dict)
    prefills: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    pending: list = dataclasses.field(default_factory=list)
    w0: float = 0.0
    w1: float = 0.0


class Session:
    """The program's serving stack for one configuration, mix and posit
    width.  Reusable across seeds: ``serve`` swaps in the seed's weights
    and restarts the scheduler; programs compile once per process."""

    def __init__(self, config: dict, mix: dict, posit_width: int | None = None,
                 trace: bool = False, patch=None):
        import jax.numpy as jnp
        from repro.core.engine import from_variant
        from repro.models.config import ModelConfig
        from repro.models.layers import Ctx
        from repro.models.transformer import Model
        from repro.numerics import NumericsContext, PrecisionPolicy
        from repro.serving import (GenerationConfig, PagedKVConfig,
                                   RequestBatcher, ServeEngine)
        self.config, self.mix, self.trace = config, mix, trace
        sv = config["serving"]
        mcfg = ModelConfig(**{k: v for k, v in config.items()
                              if k not in CONFIG_NOTES})
        ecfg = from_variant(posit_width or sv["posit_width"], sv["variant"])
        nctx = NumericsContext(policy=PrecisionPolicy.uniform(ecfg),
                               backend=sv["backend"])
        self.model = Model(mcfg, ecfg, remat=False, numerics=nctx)
        self.dtype = jnp.dtype(mcfg.dtype)
        self.gen = GenerationConfig(max_new_tokens=mix["max_len"] + 1)
        self.params = None
        eng = ServeEngine(
            self.model, None, Ctx(ecfg=ecfg, numerics=nctx),
            max_len=mix["max_len"], batch=mix["batch"], numerics=nctx,
            paged=PagedKVConfig(page_size=sv["page_size"],
                                num_pages=mix.get("num_pages")),
            cache_dtype=jnp.dtype(sv["cache_dtype"]))
        self.eng = eng
        self.batcher = RequestBatcher(eng)
        self.rec = Record()
        self._ev = 0  # batcher events already matched to prefills
        if patch is not None:  # tests break the timed path underneath
            patch(self)
        self._wrap()

    # -- stamps around the engine's calls --------------------------------
    def _wrap(self):
        from bench.trace import annotate
        eng, b = self.eng, self.batcher
        prefill, step, grow = (eng.prefill_slot, eng.step_slots,
                               eng.ensure_slot_pages)
        on = self.trace

        def prefill_slot(slot, toks, gen, key, level=0):
            t0 = time.perf_counter()
            with annotate("bench.prefill", on):
                first = prefill(slot, toks, gen, key, level)
            self.rec.pending.append((t0, time.perf_counter(), len(toks),
                                     first))
            return first

        def step_slots(gen, tok, pos, active, key, level=None):
            self._match_admissions()  # first tokens precede this step's
            st = b._state
            rows = [(s, st.slots[s].req.rid) for s in range(eng.batch)
                    if st.slots[s] is not None and active[s]]
            ctx = int(sum(int(pos[s]) + 1 for s, _ in rows))
            live = eng.kv.live_pages
            t0 = time.perf_counter()
            with annotate("bench.step", on):
                out = step(gen, tok, pos, active, key, level)
            t1 = time.perf_counter()
            for s, rid in rows:
                r = self.rec.reqs.get(rid)
                if r is not None:
                    r.times.append(t1)
                    r.tokens.append(int(out[0][s]))
            self.rec.steps.append(Step(t0, t1, len(rows), ctx, live,
                                       eng.kv.alloc.num_pages))
            return out

        def ensure_slot_pages(slot, pos):
            with annotate("bench.grow", on):
                return grow(slot, pos)

        eng.prefill_slot = prefill_slot
        eng.step_slots = step_slots
        eng.ensure_slot_pages = ensure_slot_pages

    def _match_admissions(self):
        """Give each finished prefill its request: the batcher logs one
        admit/refill event per successful prefill, in the same order."""
        evs = self.batcher.events
        while self._ev < len(evs):
            kind, rid, _, _ = evs[self._ev]
            self._ev += 1
            if kind not in ("admit", "refill"):
                continue
            t0, t1, T, first = self.rec.pending.pop(0)
            r = self.rec.reqs[rid]
            r.prefill_start, r.first = t0, t1
            r.times.append(t1)
            r.tokens.append(int(first))
            self.rec.prefills.append(Prefill(t0, t1, T, rid))

    def _submit(self, toks, budget):
        rid = self.batcher.submit(toks, max_new=budget)
        self.rec.reqs[rid] = Req(rid, toks, time.perf_counter())

    # -- set-up ----------------------------------------------------------
    def load_weights(self, seed: int):
        from bench import traffic, weights
        self.params = None
        self.eng.params = None
        self.params = weights.make(self.model, traffic.seed32(seed),
                                   self.dtype)
        self.eng.params = self.params

    def warm_up(self, skip_lengths=()):
        """Run once every program the window can call: each prefill length
        the mix can send (but those set-up's slot fill runs anyway), each
        page-table width the decode step can see, and page growth."""
        import jax
        from bench import traffic
        eng, kv, mix = self.eng, self.eng.kv, self.mix
        ps = kv.page_size
        key = jax.random.PRNGKey(0)
        for T in traffic.prefill_lengths(mix):
            if T not in skip_lengths:
                eng.prefill_slot(0, np.zeros(T, np.int32), self.gen, key)
        kv.reset()
        self.rec.pending.clear()
        B = eng.batch
        pos = np.zeros(B, np.int64)
        act = np.zeros(B, bool)
        act[0] = True
        lo = -(-min(p for p, _ in traffic.strata(mix)) // ps)
        cap = 1
        while cap < lo:
            cap *= 2
        widths = []
        while cap < kv.n_logical:
            widths.append(cap)
            cap *= 2
        widths.append(kv.n_logical)
        # the stamp wrapper reads the scheduler's slots: none are in use
        self.batcher._state = SimpleNamespace(slots=[None] * B)
        for w in widths:
            kv.alloc_slot(0, w)
            eng.step_slots(self.gen, np.zeros(B, np.int32), pos, act, key)
            kv.free_slot(0)
        kv.alloc_slot(0, 1)
        eng.ensure_slot_pages(0, ps)
        kv.reset()
        self.batcher._state = None
        self.rec = Record()

    # -- one run -----------------------------------------------------------
    def serve(self, stream, seconds: float,
              trace_dir: str | None = None) -> Record:
        """Fill the slots from ``stream``, then drive the closed loop for
        ``seconds``.  Returns the run's record (window in ``w0``/``w1``)."""
        import jax
        from bench import trace as T
        b = self.batcher
        self.rec = Record()
        self._ev = 0
        st = b._begin(self.gen, jax.random.PRNGKey(0))

        def on_complete(rid, toks):
            self._submit(*stream.next())

        for toks, budget in stream.initial():
            self._submit(toks, budget)
        b._drive(st, on_complete=on_complete, max_steps=0)
        # one untimed step through the window's own call, then the garbage
        # of set-up (compiles above all) is collected and frozen: the window
        # reads the same whether set-up compiled or loaded its programs
        b._drive(st, on_complete=on_complete, max_steps=1)
        self._match_admissions()
        jax.block_until_ready(self.eng.cache)
        gc.collect()
        gc.freeze()
        on = trace_dir is not None
        with T.capture(trace_dir) if on else contextlib.nullcontext():
            with T.annotate("bench.window", on):
                self.rec.w0 = time.perf_counter()
                deadline = self.rec.w0 + seconds
                while time.perf_counter() < deadline:
                    with T.annotate("bench.drive", on):
                        b._drive(st, on_complete=on_complete, max_steps=1)
                    self._match_admissions()
                    if not st.active.any() and not b.queue:
                        break
                self.rec.w1 = time.perf_counter()
        gc.unfreeze()
        return self.rec

    def drop_cache(self):
        """Free the KV pool (and the scheduler's hold on it) so that the
        reference has the chip's memory; the weights stay."""
        self.eng.cache = None
        self.eng._ptmpl.clear()
        self.batcher._state = None


# ---------------------------------------------------------------------------
# Output check
# ---------------------------------------------------------------------------

def check_sample(rec: Record, mix: dict, seed: int) -> list[Req]:
    """The requests the check compares: drawn from the seed among every
    request that served tokens, always with the one that served most."""
    served = [r for r in rec.reqs.values() if r.tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 1])
    k = min(len(rest), mix.get("check_requests", 16) - 1)
    pick = rng.choice(len(rest), size=k, replace=False) if k > 0 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gap(params, config: dict, sample: list[Req]) -> float:
    ref = reference(config)
    seqs = [(np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)]),
             len(r.prompt)) for r in sample]
    gaps = ref.widest_gaps(params, config, seqs)
    return max(gaps) if gaps else math.inf


# ---------------------------------------------------------------------------
# The view metric readers get
# ---------------------------------------------------------------------------

def view(rec: Record, cell: Cell, setup_s: float, peak: dict, trace,
         batch: int) -> SimpleNamespace:
    w0, w1 = rec.w0, rec.w1
    inside = lambda t: t is not None and w0 <= t <= w1  # noqa: E731
    return SimpleNamespace(
        config=cell.config, mix=cell.mix, peak=peak, trace=trace,
        batch=batch, setup_s=setup_s, w0=w0, w1=w1, window_s=w1 - w0,
        inside=inside,
        requests=list(rec.reqs.values()),
        steps=[s for s in rec.steps if s.t0 >= w0 and s.t1 <= w1],
        prefills=[p for p in rec.prefills if p.t0 >= w0 and p.t1 <= w1],
    )


def window_summary(v) -> str:
    """Where the window's wall time went, for telling a slow run's cause:
    decode steps, prefills, and the host time between them."""
    spans = sorted([(s.t0, s.t1) for s in v.steps]
                   + [(p.t0, p.t1) for p in v.prefills])
    step_s = sorted(s.t1 - s.t0 for s in v.steps)
    pre_s = sum(p.t1 - p.t0 for p in v.prefills)
    gaps = [b0 - a1 for (_, a1), (b0, _) in zip(spans, spans[1:])]
    between = v.window_s - sum(step_s) - pre_s
    return (f"window {v.window_s:.3f} s: {len(step_s)} steps "
            f"{sum(step_s):.3f} s (median {np.median(step_s or [0]):.4f}, "
            f"longest {max(step_s or [0]):.4f}), {len(v.prefills)} prefills "
            f"{pre_s:.3f} s, host between {between:.3f} s (longest gap "
            f"{max(gaps or [0]):.4f})")


# ---------------------------------------------------------------------------
# A whole run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, compiles: Compiles, *, posit_width=None,
             patch=None, out=sys.stdout,
             err=sys.stderr) -> dict:
    import jax
    from bench import trace as T
    from bench import traffic
    dev = jax.devices()[0]
    # off the chip (the CPU rehearsal) there is no peak to compare with
    peak = peak_of(dev.device_kind) if dev.platform == "tpu" else None
    collector = Collections()
    t = [time.perf_counter()]
    sess = Session(cell.config, cell.mix, posit_width, trace=trace,
                   patch=patch)
    sess.load_weights(seed)
    jax.block_until_ready(sess.params)
    t.append(time.perf_counter())
    stream = traffic.Stream(cell.mix, seed, cell.config["vocab"])
    fill = traffic.Stream(cell.mix, seed, cell.config["vocab"]).initial()
    sess.warm_up(skip_lengths={len(toks) for toks, _ in fill})
    t.append(time.perf_counter())
    tmp = tempfile.TemporaryDirectory() if trace else None
    rec = sess.serve(stream, seconds, trace_dir=tmp.name if tmp else None)
    setup_s = rec.w0 - t_start
    n_comp = compiles.between(rec.w0, rec.w1)
    print(f"set-up {setup_s:.1f} s: start {t[0] - t_start:.1f}, weights "
          f"{t[1] - t[0]:.1f}, warm-up {t[2] - t[1]:.1f}, slot fill and "
          f"tracer {rec.w0 - t[2]:.1f}; {compiles.between(t_start, rec.w0)} "
          f"programs compiled or loaded", file=err)
    n_gc, gc_s = collector.between(rec.w0, rec.w1)
    collector.close()
    print(f"python garbage collections inside the window: {n_gc}, "
          f"{gc_s:.3f} s", file=err)
    print(f"programs compiled or loaded inside the window: {n_comp}",
          file=err, flush=True)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    tr = None
    if tmp is not None:
        t0 = time.perf_counter()
        tr = T.load(tmp.name)
        tmp.cleanup()
        print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
              file=err)
    v = view(rec, cell, setup_s, peak, tr, sess.eng.batch)
    print(window_summary(v), file=err, flush=True)
    metrics = {}
    for m in cell.metrics:
        val = metric_reader(m["name"])(v)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    in_window = [r for r in rec.reqs.values()
                 if v.inside(r.submit) or any(v.inside(t) for t in r.times)]
    statuses = sess.batcher.statuses
    failed = sum(statuses.get(r.rid, "ok") != "ok" for r in in_window)

    sess.drop_cache()
    sample = check_sample(rec, cell.mix, seed)
    gap = logit_gap(sess.params, cell.config, sample)
    limit = (cell.limits or {}).get("logit_gap", {}).get("limit")
    correct = bool(limit is not None and gap <= limit and failed == 0)
    result = {
        "correct": correct, "attempted": len(in_window), "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem_peak},
    }
    if tr is not None:
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["check"] = {"logit_gap": {"value": gap, "limit": limit}}
    checked = sum(len(r.tokens) for r in sample)
    print(f"checked {checked} served tokens of {len(sample)} requests "
          f"against the reference", file=err)
    print(f"logit_gap {gap} limit {limit}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
