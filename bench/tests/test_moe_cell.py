"""The sparse-expert cell's reference and per-layer readers on the CPU:
``bench/references/moe_decoder.py``'s output check, the four readers of
``bench/moe_shapes.py``'s counts on a view built by hand, and a traced run
of a tiny expert cell through the harness."""
import io
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, moe_shapes, shapes, trace, traffic, weights
from bench.tests import tiny_moe

# readings of the tiny expert cell on the CPU: Posit-16 gaps 0.11-0.15
# over two seeds (bf16 activations flip a few routing choices against the
# f32 reference), the Posit-8 control 0.65-0.79
TINY_LIMIT = 0.4


@pytest.fixture(scope="module")
def tiny_params():
    sess = harness.Session(tiny_moe.CONFIG, tiny_moe.MIX)
    return sess, weights.make(sess.model, traffic.seed32(7), sess.dtype)


def _greedy(ref, params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = ref.logits(params, cfg, np.asarray([toks], np.int32))
        toks.append(int(np.argmax(logits[0, -1])))
    return np.asarray(toks, np.int32)


def test_the_cell_states_its_published_config():
    """The chip cell's file holds the published config's keys at its top
    level beside the program's, the program builds it, and the keys
    ``BENCHMARK.json`` lists as reduced are those ``published`` gives the
    source's values of."""
    from repro.models.config import ModelConfig
    cell = harness.load_cell(tiny_moe.CELL, False)
    cfg = ModelConfig(**{k: v for k, v in cell.config.items()
                         if k not in harness.CONFIG_NOTES})
    assert (cfg.n_layers, cfg.n_experts, cfg.n_routed) == (16, 16, 64)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    conf, = [c for c in spec["configs"]
             if c["file"].endswith("/mellum2-l16-e16.json")]
    assert sorted(conf["reduced"]) == sorted(cell.config["published"])


def test_widest_gaps_reads_the_served_tokens_logits(tiny_params):
    """Served tokens that are the reference's own greedy choices read a
    gap of 0; one replaced token reads its logit's distance below the
    best at its position."""
    _, params = tiny_params
    cfg = tiny_moe.CONFIG
    ref = harness.reference(cfg)
    prompt = np.random.default_rng(0).integers(0, cfg["vocab"], 20)
    toks = _greedy(ref, params, cfg, prompt, 6)
    assert ref.widest_gaps(params, cfg, [(toks, len(prompt))]) == [0.0]
    bad = toks.copy()
    bad[23] = (bad[23] + 1) % cfg["vocab"]
    logits = np.asarray(ref.logits(params, cfg, bad[None, :-1]))[0, 22]
    want = float(logits.max() - logits[bad[23]])
    got = ref.widest_gaps(params, cfg, [(bad, len(prompt)),
                                        (toks, len(prompt))])
    assert got[0] == pytest.approx(want, rel=1e-5) and want > 0
    assert got[1] == 0.0


class _Batcher:
    """What ``moe_shapes.expert_calls`` reads of a live scheduler."""


@pytest.fixture()
def view(monkeypatch):
    from repro.serving import engine
    monkeypatch.setattr(engine, "RequestBatcher", _Batcher)
    b = _Batcher()
    # one call before the window, two prefills and three steps inside
    b.expert_log = [(0.5, "decode", 99, 999), (1.2, "prefill", 100, 768),
                    (1.4, "decode", 40, 192), (1.6, "decode", 36, 192),
                    (1.8, "prefill", 60, 384), (1.9, "decode", 50, 192)]
    ops = {"/device:TPU:0": [("%logmac_experts.1 = f32[]", 1.1, 1.3),
                             ("%logmac.2 = f32[]", 1.3, 1.5),
                             ("%logmac_experts.3 = f32[]", 1.5, 1.6)]}
    tr = trace.Trace(ops, [("bench.window", 1.0, 2.0)])
    steps = [harness.Step(t - 0.1, t, 4, 100, 10, 20) for t in (1.4, 1.6,
                                                               1.9)]
    prefills = [harness.Prefill(1.1, 1.2, 32, 0), harness.Prefill(1.7, 1.8,
                                                                  16, 1)]
    reqs = [SimpleNamespace(times=[1.2, 1.4, 1.6, 2.5])]
    yield b, SimpleNamespace(
        config=tiny_moe.CONFIG, mix=tiny_moe.MIX, trace=tr, batch=4,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}, w0=1.0,
        w1=2.0, window_s=1.0, inside=lambda t: 1.0 <= t <= 2.0,
        steps=steps, prefills=prefills, requests=reqs)
    del b


def test_readers_on_a_view(view):
    b, v = view
    calls = moe_shapes.expert_calls(v)
    assert calls == [(k, r, s) for t, k, r, s in b.expert_log[1:]]
    rows, slots = 286, 1728
    fill = harness.metric_reader("expert_fill_share")(v)
    assert fill == pytest.approx(100 * rows / slots)

    c = tiny_moe.CONFIG
    d, f, L, E = c["d_model"], c["d_ff"], c["n_layers"], c["n_experts"]
    weights_bytes = L * E * 3 * d * f * 2.0   # Posit-16 words
    need = sum(max(2.0 * r * 3 * d * f / 1e12,
                   (weights_bytes + r * ((2 * d + f) * 2 + (2 * f + d) * 4))
                   / 1e9) for _, r, _ in calls)
    assert harness.metric_reader("expert_roofline")(v) == pytest.approx(
        100 * need / 0.3)

    mm = [(d, 4 * 32), (d, 2 * 32), (d, 2 * 32), (4 * 32, d)] * L
    head = (d, c["vocab"])
    logmac_need = sum(
        max(2.0 * M * K * N / 1e12, ((M * K + K * N) * 2 + 4.0 * M * N) / 1e9)
        for M, K, N in [(4, K, N) for K, N in mm + [head]] * 3
        + [(p, K, N) for p in (32, 16) for K, N in mm] + [(1, *head)] * 2)
    assert harness.metric_reader("moe_logmac_roofline")(v) == pytest.approx(
        100 * logmac_need / 0.2)

    per_token = (2.0 * sum(k * n for k, n in mm + [head])
                 + rows / (slots / E) * L * 2 * 3 * d * f)
    assert harness.metric_reader("moe_decode_mfu")(v) == pytest.approx(
        100 * 3 * per_token / 1e12)
    assert shapes.posit_bytes(c) == 2


def test_readers_read_nothing_from_a_program_without_counts(view,
                                                            monkeypatch):
    """A program with no expert log (the parent of the change that added
    it): the counter-based readers report nothing and raise nothing."""
    from repro.serving import engine
    b, v = view
    del b.expert_log
    assert moe_shapes.expert_calls(v) is None
    monkeypatch.delattr(engine, "RequestBatcher")
    for name in ("expert_roofline", "expert_fill_share", "moe_decode_mfu"):
        assert harness.metric_reader(name)(v) is None


def test_traced_run_prints_the_expert_metrics():
    """The tiny expert cell end to end, traced: correct, and the counter
    metric is printed; those against the chip's peaks are left out on the
    CPU, never reported as 0."""
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(tiny_moe.cell(True, TINY_LIMIT), 2**31 + 11, 2.0,
                           True, time.perf_counter(), harness.Compiles(),
                           out=out, err=err)
    assert res["correct"] is True, err.getvalue()
    got = res["metrics"]
    # 2 of 8 experts per token, 4 of them held: a quarter of the slots
    assert 15 < got["expert_fill_share"]["value"] < 35
    assert {"decode_step_ms", "kv_pages_used_share"} <= set(got)
    assert not {"expert_roofline", "moe_logmac_roofline",
                "moe_decode_mfu"} & set(got)
    jax.clear_caches()
