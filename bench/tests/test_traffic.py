"""The generator gives every seed the same work in the same order, and
the harness refuses data it would not run."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))
# slots started at decoded contexts ("context" stagger), as a long-stream
# mix would ask; no committed mix uses it yet
CONTEXT_MIX = {
    "clients": 8, "batch": 8, "max_len": 1280,
    "prompt": {"dist": "uniform", "min": 100, "max": 250,
               "lengths": [128, 192, 256]},
    "output": {"dist": "uniform", "min": 500, "max": 1000},
    "stagger": "context", "initial_contexts": [256, 512, 768, 1024],
}


def test_context_stagger_is_seed_independent():
    a, b = (traffic.Stream(CONTEXT_MIX, s, 1000).initial()
            for s in (3, 2**31 + 9))
    assert [(len(t), o) for t, o in a] == [(len(t), o) for t, o in b]
    for toks, budget in a:
        assert len(toks) in CONTEXT_MIX["initial_contexts"]
        assert 1 <= budget and len(toks) + budget <= 1281


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_sizes_for_every_seed(path):
    mix = json.loads(path.read_text())
    n = mix.get("strata", 64)
    sizes = []
    for seed in (1, 2**31 + 5):
        s = traffic.Stream(mix, seed, 1000)
        first = [(len(t), o) for t, o in s.initial()]
        sizes.append(first + [(len(t), o) for t, o in
                              (s.next() for _ in range(3 * n))])
    assert sizes[0] == sizes[1]
    rounds = Counter(sizes[0][mix["clients"]:])
    assert rounds == Counter({k: 3 * v for k, v in
                              Counter(traffic.strata(mix)).items()})
    for (p, o) in sizes[0]:
        assert p in traffic.prefill_lengths(mix)
        assert 1 <= o <= mix["output"]["max"]
        assert p + o <= mix["max_len"] + 1


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seed_fixes_requests(path):
    mix = json.loads(path.read_text())
    a, b, c = (traffic.Stream(mix, s, 1000) for s in (7, 7, 8))
    ia, ib, ic = a.initial(), b.initial(), c.initial()
    assert len(ia) == mix["clients"]
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(ia, ib))
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(ia, ic))
    assert sorted(len(t) for t, _ in ia) == sorted(len(t) for t, _ in ic)
    for toks, budget in ia:
        assert 1 <= budget and len(toks) + budget <= mix["max_len"] + 1
        assert len(toks) in traffic.prefill_lengths(mix)


def test_seed32_keeps_high_bits():
    assert traffic.seed32(5) != traffic.seed32(2**32 + 5)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_harness_refuses_keys_it_does_not_run(path):
    from bench import harness
    config = json.loads((path.parents[1] / "configs" /
                         "yi-6b-l16.json").read_text())
    mix = json.loads(path.read_text())
    harness.check_data(config, mix)
    for bad_config, bad_mix in (
            (dict(config, norm="layernorm"), mix),
            (dict(config, tie_embeddings=False), mix),
            (dict(config, mlp="gelu_gated"), mix),
            (config, dict(mix, think_s=1.0)),
            (config, dict(mix, prompt=dict(mix["prompt"], mean=90)))):
        with pytest.raises(ValueError, match="not run by this harness"):
            harness.check_data(bad_config, bad_mix)
