"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into the requests a closed loop of clients sends.

Every seed sends the same requests in the same order: prompt and output
lengths are the distribution's quantiles at ``strata`` evenly spaced
probabilities, served in rounds, each round one fixed permutation of all
strata.  The seed draws the prompts' token ids (and the weights), not the
work: in a closed loop the order of sizes decides which requests share an
admission round, so a seed that reordered them would change the work.

Mix keys:
  clients, batch, max_len      closed loop of ``clients`` users (zero think
                               time) on ``batch`` slots of ``max_len`` tokens
  num_pages                    KV pool pages, reserved ones included
                               (default: every slot at ``max_len``)
  prompt, output               {"dist": "lognormal", "median", "sigma"} or
                               {"dist": "uniform"}, both with "min"/"max";
                               prompt "lengths" snaps a drawn length up to the
                               next listed one (each listed length is one
                               compiled prefill program)
  stagger                      how set-up starts the slots in steady state:
                               "budget" gives the first request of each
                               client a remaining output budget spread over
                               (0, output], so completions are spread from
                               the first step; "context" also gives it a
                               context already decoded, from
                               "initial_contexts" (snapped to the nearest)
  strata                       sizes per round (default 64)
  check_requests               requests the output check compares
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# pairs prompt stratum i with output stratum (i * _PAIRING) mod strata, so
# long prompts do not always meet long outputs; odd, so a bijection for the
# power-of-two strata the mixes use (checked in Stream.__init__)
_PAIRING = 37
# the fixed order of sizes every seed sends
_ORDER_SEED = 0

KEYS = frozenset({"clients", "batch", "max_len", "num_pages", "prompt",
                  "output", "stagger", "initial_contexts", "strata",
                  "check_requests"})
LENGTH_KEYS = frozenset({"dist", "median", "sigma", "min", "max", "lengths"})


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from any whole seed (JAX keeps only 32 bits)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def _quantile(spec: dict, u: float) -> float:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return min(max(x, lo), hi)


def _snap_up(x: float, lengths) -> int:
    for n in sorted(lengths):
        if n >= x:
            return int(n)
    return int(max(lengths))


def prompt_length(spec: dict, u: float) -> int:
    x = _quantile(spec, u)
    return _snap_up(x, spec["lengths"]) if "lengths" in spec else math.ceil(x)


def strata(mix: dict) -> list[tuple[int, int]]:
    """(prompt length, output length) of every stratum: seed-independent."""
    n = mix.get("strata", 64)
    if math.gcd(_PAIRING, n) != 1:
        raise ValueError(f"strata={n} must be coprime with {_PAIRING}")
    out = []
    for i in range(n):
        p = prompt_length(mix["prompt"], (i + 0.5) / n)
        o = round(_quantile(mix["output"], ((i * _PAIRING) % n + 0.5) / n))
        out.append((p, max(1, int(o))))
    return out


def prefill_lengths(mix: dict) -> list[int]:
    """Every prompt length set-up and window can prefill (to warm up)."""
    lengths = {p for p, _ in strata(mix)}
    if mix.get("stagger") == "context":
        lengths |= set(int(c) for c in mix["initial_contexts"])
    return sorted(lengths)


class Stream:
    """The requests of one run: ``initial()`` for the slots set-up fills,
    then ``next()`` for each request a client sends after a completion."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)          # token ids
        self.order = np.random.default_rng(_ORDER_SEED)  # sizes: fixed
        self.sizes = strata(mix)
        self._round: list[tuple[int, int]] = []
        for p, o in self.sizes:
            if p + o > mix["max_len"] + 1:
                raise ValueError(f"prompt {p} + output {o} exceeds max_len "
                                 f"{mix['max_len']}")

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def _size(self) -> tuple[int, int]:
        if not self._round:
            order = self.order.permutation(len(self.sizes))
            self._round = [self.sizes[i] for i in order]
        return self._round.pop()

    def next(self) -> tuple[np.ndarray, int]:
        """(prompt tokens, output budget) of the next request sent."""
        p, o = self._size()
        return self._tokens(p), o

    def initial(self) -> list[tuple[np.ndarray, int]]:
        """One in-progress request per client, as in steady state."""
        mix = self.mix
        n = mix["clients"]
        fracs = (self.order.permutation(n) + 0.5) / n
        if mix["stagger"] == "budget":
            # evenly spaced strata, so every seed starts from the same sizes
            m = len(self.sizes)
            picks = [self.sizes[int((j + 0.5) * m / n)]
                     for j in self.order.permutation(n)]
            return [(self._tokens(p), max(1, math.ceil(f * o)))
                    for (p, o), f in zip(picks, fracs)]
        if mix["stagger"] != "context":
            raise ValueError(f"unknown stagger {mix['stagger']!r}")
        return [(self._tokens(c), b) for c, b in
                (self._context_state(f) for f in fracs)]

    def _context_state(self, frac: float) -> tuple[int, int]:
        """(context, remaining budget) at quantile ``frac`` of the steady
        state: a request caught ``g`` tokens into its output, requests
        weighted by their output length (longer ones are in flight longer).
        Computed from the strata alone, so it does not depend on the seed."""
        mix = self.mix
        states = []
        for p, o in self.sizes:
            for g in range(0, o, max(1, o // 64)):
                states.append((p + g, o - g, o))
        states.sort()
        w = np.asarray([s[2] for s in states], float)
        cdf = np.cumsum(w) / w.sum()
        ctx, rem, _ = states[int(np.searchsorted(cdf, frac))]
        allowed = sorted(int(c) for c in mix["initial_contexts"])
        c = min(allowed, key=lambda a: (abs(a - ctx), a))
        return c, max(1, min(rem, mix["max_len"] + 1 - c))
