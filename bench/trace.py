"""Profiler trace -> device busy time, kernel time, idle gaps by host span.

``capture`` records a JAX profiler trace; ``load`` reduces its
``.xplane.pb`` to plain lists:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, one
  ``(name, start_s, end_s)`` per executed HLO op (a Pallas kernel is the
  custom call named after its wrapper: ``%logmac.3 = ...``);
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
  every host event whose name starts with ``bench.``.

The device clock in the trace is offset from the host's by some
milliseconds.  ``load`` moves device times onto the host clock by pairing
the n-th host ``tpu::System::Execute`` with the n-th device module
(``XLA Modules`` line) and taking the 90th percentile of host minus device
start: a launch reaches the device after the host issues it, and queued
launches only start later, so the high end is the launch itself.

``Trace`` is the reduction every metric reads; ``Trace.to_json`` /
``from_json`` keep it as a small file (``bench/tests`` checks the
reduction on one recorded from the chip).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import math
import os
import re

import numpy as np

_EXEC = "tpu::System::Execute"
# control flow: their events span the ops of their bodies, which the op
# line also holds, so they are left out of the op totals (not of busy time)
CONTAINERS = ("while", "conditional", "call")
_OP_NAME = re.compile(r"^%?([^ ]+?)(?:\.\d+)? = ")


def op_kind(name: str) -> str:
    """``%logmac.3 = f32[..] custom-call(..)`` -> ``logmac``."""
    m = _OP_NAME.match(name)
    return m.group(1) if m else name.split(" ")[0].lstrip("%")


@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no Python function events
    opts.host_tracer_level = 2     # keeps the runtime's Execute events
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, ops: dict, spans: list):
        # ops: {device: [(name, start_s, end_s), ...]} on the host clock
        self.ops = {d: sorted(v, key=lambda o: o[1]) for d, v in ops.items()}
        self.spans = sorted(spans, key=lambda s: s[1])
        win = [s for s in self.spans if s[0] == "bench.window"]
        if not win:
            raise ValueError("trace holds no bench.window span")
        self.t0, self.t1 = win[0][1], win[0][2]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _in_window(self, dev):
        for n, s, e in self.ops[dev]:
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                yield n, s, e

    def _busy(self, dev):
        return _merge((s, e) for _, s, e in self._in_window(dev))

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the traced chips."""
        if not self.ops:
            return 0.0
        return float(np.mean([sum(e - s for s, e in self._busy(d))
                              for d in self.ops]))

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of ops whose kind matches ``pattern`` (a regex,
        matched at the start), summed over chips."""
        rx = re.compile(pattern)
        return sum(e - s for d in self.ops for n, s, e in self._in_window(d)
                   if rx.match(op_kind(n)))

    def top_ops(self, k: int = 10) -> list:
        """Device seconds by op kind, summed over chips, largest first."""
        tot: dict[str, float] = {}
        for d in self.ops:
            for n, s, e in self._in_window(d):
                kind = op_kind(n)
                if kind not in CONTAINERS:
                    tot[kind] = tot.get(kind, 0.0) + (e - s)
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle device time inside the window, summed by the innermost
        benchmark span the host was in at each gap's midpoint."""
        groups = {}
        for name, s, e in self.spans:
            if name != "bench.window":
                groups.setdefault(name, []).append((s, e))
        # spans of one name never overlap (one host thread), so a bisect
        # per name finds the one that can hold a point
        index = {n: ([s for s, _ in v], v) for n, v in groups.items()}
        tot: dict[str, float] = {}
        for d in self.ops:
            edges = [self.t0] + [x for iv in self._busy(d) for x in iv] \
                + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                m, best, name = 0.5 * (a + b), math.inf, "bench.window"
                for n, (starts, iv) in index.items():
                    i = bisect.bisect_right(starts, m) - 1
                    if i >= 0 and iv[i][1] >= m and iv[i][1] - iv[i][0] < best:
                        best, name = iv[i][1] - iv[i][0], n
                tot[name] = tot.get(name, 0.0) + (b - a)
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    def to_json(self) -> dict:
        return {"ops": self.ops, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(o) for o in v] for k, v in d["ops"].items()},
                   [tuple(s) for s in d["spans"]])


def _shift(host_exec, modules) -> float:
    n = min(len(host_exec), len(modules))
    if n == 0:
        return 0.0
    d = np.asarray(sorted(host_exec)[:n]) - np.asarray(sorted(modules)[:n])
    return float(np.percentile(d, 90))


def load(log_dir: str) -> Trace:
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    spans, host_exec, devices = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [e.start_ns * 1e-9 for e in line.events]
            devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
                    elif e.name == _EXEC:
                        host_exec.append(e.start_ns * 1e-9)
    out = {}
    for dev, (ops, modules) in devices.items():
        if not ops:
            continue
        sh = _shift(host_exec, modules)
        out[dev] = [(n, s + sh, e + sh) for n, s, e in ops]
    return Trace(out, spans)
