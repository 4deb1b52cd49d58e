"""The program's own spans and kernel names in a trace recorded on a v5e.

``program_spans.xplane.pb``: a profile of the tiny cell's configuration
(``tiny.CONFIG``: two layers, the pallas backend at Posit-16, uint16 KV
pages) draining three requests at batch 2, one of them a refill, every
program compiled before the capture.  Recorded on a TPU v5e, and cut to
what the reduction reads (``trim``), with

    python3 bench/tests/test_program_spans.py <out.xplane.pb>

The benchmark's reduction (``trace.load``) finds each kernel its roofline
readers look for under the kernel's own name, and the serving path's
``serve.*`` spans lie on the same clock as the device ops they launch.
"""
import glob
import importlib.util
import os
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "program_spans.xplane.pb"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
N_LAYERS = 2  # tiny.CONFIG's
EXEC = "tpu::System::Execute"


def _kernel_regex(metric: str) -> str:
    spec = importlib.util.spec_from_file_location(
        f"roofline_{metric}", METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


def _serve_events(path):
    """(name, start_s, end_s, args) of every ``serve.`` host event."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
            dict(e.stats))
           for plane in pd.planes if plane.name.startswith("/host:CPU")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def reduced():
    from bench import trace as T
    with tempfile.TemporaryDirectory() as d:
        prof = Path(d) / "plugins" / "profile" / "run"
        prof.mkdir(parents=True)
        shutil.copy(FIXTURE, prof / "host.xplane.pb")
        return T.load(d)


@pytest.fixture(scope="module")
def serve():
    return _serve_events(FIXTURE)


def _named(serve, name):
    return [s for s in serve if s[0] == name]


@pytest.mark.parametrize("metric,kernel", [
    ("logmac_roofline", "logmac"), ("codec_roofline", "posit_encode"),
    ("paged_decode_roofline", "paged_flash_decode")])
def test_rooflines_find_their_kernels(reduced, metric, kernel):
    from bench import trace as T
    rx = _kernel_regex(metric)
    kinds = {T.op_kind(n) for n, _, _ in reduced.ops["/device:TPU:0"]}
    assert kernel in kinds
    assert reduced.kernel_s(rx) > 0
    assert "tpu_custom_call" not in kinds


def test_spans_are_the_programs_constants(serve):
    from repro.serving import spans
    assert {s[0] for s in serve} == set(spans.NAMES)
    steps = _named(serve, spans.DECODE)
    assert len(steps) == len(_named(serve, spans.GROW)) > 0
    assert all(a["live_pages"] <= a["pool_pages"] and a["rows"] > 0
               for *_, a in steps)
    admits = _named(serve, spans.ADMIT)
    assert [a["rid"] for *_, a in admits] == [0, 1, 2]
    done = sorted(a["rid"] for *_, a in _named(serve, spans.ON_COMPLETE))
    assert done == [0, 1, 2]


@pytest.mark.parametrize("inner,outer", [
    ("serve.decode.wait", "serve.decode"),
    ("serve.decode.table", "serve.decode"),
    ("serve.prefill", "serve.admit"),
    ("serve.prefill.wait", "serve.prefill")])
def test_spans_nest(serve, inner, outer):
    outers = _named(serve, outer)
    assert _named(serve, inner)
    for _, s, e, _ in _named(serve, inner):
        assert any(a <= s and e <= b for _, a, b, _ in outers)


def test_decode_kernels_lie_inside_decode_spans(reduced, serve):
    """On the clock ``trace.load`` puts the device on, every paged-decode
    kernel runs inside a ``serve.decode`` span (within 1 ms, the launch
    latency the shift leaves), one per layer and step."""
    from bench import trace as T
    steps = [(s, e) for _, s, e, _ in _named(serve, "serve.decode")]
    ops = [(s, e) for n, s, e in reduced.ops["/device:TPU:0"]
           if T.op_kind(n) == "paged_flash_decode"]
    assert len(ops) == N_LAYERS * len(steps)
    for s, e in ops:
        assert any(a - 1e-3 <= s and e <= b + 1e-3 for a, b in steps)


def trim(src: str, dst: str) -> None:
    """Keep what ``trace.load`` and these tests read: the device planes'
    ``XLA Ops`` and ``XLA Modules`` lines, op names up to their opcode, and
    the host events named ``serve.*``, ``bench.*`` or
    ``tpu::System::Execute``.  (The HLO protos alone take megabytes.)"""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(Path(src).read_bytes())
    planes = [p for p in xs.planes
              if p.name.startswith(("/device:TPU:", "/host:CPU"))]
    del xs.planes[:]
    for p in planes:
        q = xs.planes.add()
        q.CopyFrom(p)
        md = q.event_metadata
        keep = (lambda e: md[e.metadata_id].name.startswith(
            ("serve.", "bench.")) or md[e.metadata_id].name == EXEC)
        lines = []
        for line in q.lines:
            if q.name.startswith("/device:"):
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines.append(line)
            elif any(keep(e) for e in line.events):
                events = [e for e in line.events if keep(e)]
                del line.events[:]
                line.events.extend(events)
                lines.append(line)
        del q.lines[:]
        q.lines.extend(lines)
        del q.stats[:]
        used = {e.metadata_id for line in q.lines for e in line.events}
        for k in list(md):
            if k not in used:
                del md[k]
                continue
            del md[k].stats[:]
            head, sep, tail = md[k].name.partition(" = ")
            md[k].name = head + sep + tail.split("(")[0][:48]
            md[k].display_name = ""
    Path(dst).write_bytes(xs.SerializeToString())


def record(out: str) -> None:
    """Record the fixture on a chip: warm every program, then trace the
    same drain inside a ``bench.window`` span."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import trace as T
    from bench import weights
    from bench.harness import CONFIG_NOTES
    from bench.tests.tiny import CONFIG
    from repro.core.engine import from_variant
    from repro.models.config import ModelConfig
    from repro.models.layers import Ctx
    from repro.models.transformer import Model
    from repro.numerics import NumericsContext, PrecisionPolicy
    from repro.serving import (GenerationConfig, PagedKVConfig,
                               RequestBatcher, ServeEngine)
    sv = CONFIG["serving"]
    ecfg = from_variant(sv["posit_width"], sv["variant"])
    nctx = NumericsContext(policy=PrecisionPolicy.uniform(ecfg),
                           backend=sv["backend"])
    model = Model(ModelConfig(**{k: v for k, v in CONFIG.items()
                                 if k not in CONFIG_NOTES}),
                  ecfg, remat=False, numerics=nctx)
    eng = ServeEngine(model, weights.make(model, 7, jnp.bfloat16),
                      Ctx(ecfg=ecfg, numerics=nctx), max_len=64, batch=2,
                      numerics=nctx,
                      paged=PagedKVConfig(page_size=sv["page_size"]),
                      cache_dtype=jnp.dtype(sv["cache_dtype"]))

    def drain():
        b = RequestBatcher(eng)
        rng = np.random.default_rng(0)
        for n in (20, 9, 30):
            b.submit(rng.integers(1, CONFIG["vocab"], n), max_new=4)
        b.run(GenerationConfig(max_new_tokens=4), lambda rid, toks: None,
              key=jax.random.PRNGKey(1))
        jax.block_until_ready(eng.cache)
        return b.stats

    drain()
    with tempfile.TemporaryDirectory() as d:
        with T.capture(d), T.annotate("bench.window", True):
            stats = drain()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        trim(path, out)
    print(stats, os.path.getsize(out), "bytes on", jax.devices()[0])


if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    record(sys.argv[1])
