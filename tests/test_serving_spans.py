"""The serving path's profiler spans and counters.

A paged drain with refills and page growth runs under ``jax.profiler``;
the spans are read back from the ``.xplane.pb`` the profiler writes and
checked against the batcher's own events and stats.
"""
import glob
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EulerConfig
from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.transformer import Model
from repro.serving import (GenerationConfig, PagedKVConfig, RequestBatcher,
                           ServeEngine, spans)

CFG = ModelConfig(name="spans", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  loss_chunk=32, q_chunk=32, kv_chunk=32)
GEN = GenerationConfig(max_new_tokens=12)


@pytest.fixture(scope="module")
def model_params():
    m = Model(CFG, EulerConfig(mode="exact"), remat=False)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(model_params, max_len=64):
    m, params = model_params
    return ServeEngine(m, params, Ctx(ecfg=m.ecfg), max_len=max_len,
                       batch=2, cache_dtype=jnp.float32,
                       paged=PagedKVConfig(page_size=8))


def _drain(eng, lengths, completed=None):
    b = RequestBatcher(eng)
    rng = np.random.default_rng(0)
    for n in lengths:
        b.submit(rng.integers(1, CFG.vocab, n).astype(np.int32),
                 max_new=GEN.max_new_tokens)
    on_complete = None if completed is None else (
        lambda rid, toks: completed.append(rid))
    return b.run(GEN, on_complete=on_complete, key=jax.random.PRNGKey(1)), b


def _read_spans(log_dir):
    """(name, start_ns, end_ns, args) of every ``serve.`` host event."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _named(recs, name):
    return [r for r in recs if r[0] == name]


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


@pytest.fixture(scope="module")
def traced(model_params, tmp_path_factory):
    eng = _engine(model_params)
    _drain(eng, [20, 9])  # compile every program outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    completed = []
    with jax.profiler.trace(log_dir):
        res, b = _drain(eng, [20, 9, 30, 5, 14], completed)
    return _read_spans(log_dir), res, b, completed, eng


def test_every_span_is_a_named_constant(traced):
    recs = traced[0]
    assert {r[0] for r in recs} == set(spans.NAMES)


def test_one_decode_span_per_step(traced):
    recs, _, b, _, eng = traced
    decode = _named(recs, spans.DECODE)
    assert len(decode) == b.stats["steps"] > 0
    for _, _, _, a in decode:
        assert 0 < a["rows"] <= eng.batch
        assert 0 < a["live_pages"] <= a["pool_pages"]
        assert a["pool_pages"] == eng.kv.alloc.num_pages


@pytest.mark.parametrize("inner,outer", [
    (spans.DECODE_WAIT, spans.DECODE), (spans.DECODE_TABLE, spans.DECODE),
    (spans.PREFILL, spans.ADMIT), (spans.PREFILL_WAIT, spans.PREFILL)])
def test_spans_nest(traced, inner, outer):
    recs = traced[0]
    outers = _named(recs, outer)
    assert _named(recs, inner)
    assert all(_inside(r, outers) for r in _named(recs, inner))


def test_one_admit_span_per_admission_with_its_rid(traced):
    recs, _, b, _, _ = traced
    admitted = [rid for kind, rid, _, _ in b.events
                if kind in ("admit", "refill")]
    assert b.stats["refills"] >= 1
    assert [a["rid"] for *_, a in _named(recs, spans.ADMIT)] == admitted
    assert b.stats["prefills"] == len(admitted)
    assert b.stats["prefill_tokens"] == sum(
        a["length"] for *_, a in _named(recs, spans.PREFILL))


def test_on_complete_spans_match_completed_requests(traced):
    recs, res, _, completed, _ = traced
    done = _named(recs, spans.ON_COMPLETE)
    assert len(done) == len(res) == len(completed)
    assert sorted(a["rid"] for *_, a in done) == sorted(completed)
    outers = _named(recs, spans.RETIRE) + _named(recs, spans.ADMIT)
    assert all(_inside(r, outers) for r in done)
    retired = sum(a["retired"] for *_, a in _named(recs, spans.RETIRE))
    assert retired == len(done)


def test_grow_spans_sum_to_pages_grown(traced):
    recs, _, b, _, _ = traced
    grow = _named(recs, spans.GROW)
    assert len(grow) == b.stats["steps"]
    assert sum(a["pages"] for *_, a in grow) == b.stats["pages_grown"] > 0


def test_compiles_counted_per_drain(model_params, caplog):
    eng = _engine(model_params, max_len=48)  # fresh programs
    with caplog.at_level(logging.INFO, logger="repro.serving"):
        _, b1 = _drain(eng, [20, 9, 30])
    assert b1.stats["compiles"] >= 1
    assert any("compiled" in r.getMessage() and "decode step 0"
               in r.getMessage() for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="repro.serving"):
        _, b2 = _drain(eng, [20, 9, 30])
    assert b2.stats["compiles"] == 0
    assert not any("compiled" in r.getMessage() for r in caplog.records)


def test_recompile_names_its_step(model_params, caplog):
    eng = _engine(model_params, max_len=48)
    _drain(eng, [20, 9])
    # the third request refills at a prompt length new to the engine: its
    # prefill compiles at the step of its admission
    with caplog.at_level(logging.INFO, logger="repro.serving"):
        _, b = _drain(eng, [20, 9, 33])
    (step,) = [st for kind, _, _, st in b.events if kind == "refill"]
    assert step > 0 and b.stats["compiles"] >= 1
    assert [r.getMessage() for r in caplog.records
            if "compiled" in r.getMessage()] == [
        f"{b.stats['compiles']} program(s) compiled or loaded at decode "
        f"step {step}"]


def test_serve_cli_writes_a_trace(tmp_path, capsys, monkeypatch):
    from repro.launch import serve
    # serve.main then leaves the compilation cache settings alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = serve.main(["--arch", "yi-6b", "--euler", "exact",
                      "--backend", "exact", "--requests", "3",
                      "--max-new", "4", "--batch", "2", "--max-len", "64",
                      "--paged", "--trace-dir", str(tmp_path / "trace")])
    names = {r[0] for r in _read_spans(str(tmp_path / "trace"))}
    assert spans.DECODE in names
    printed = capsys.readouterr().out
    s = out["stats"]
    assert s["prefills"] == 3
    assert (f"{s['prefills']} prefills, {s['pages_grown']} pages grown, "
            f"{s['compiles']} programs compiled") in printed
