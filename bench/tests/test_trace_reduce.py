"""The trace reduction, checked on traces recorded on a TPU v5e.

``batch_decode_trace.json``: 1.16 s of the batch_decode cell's traced
window (two scheduler calls: decode steps, a refill prefill, page growth),
reduced by ``trace.load`` on the chip, op names cut to their kind.
``small.xplane.pb``: a raw profile of four timed matmul steps through the
codec and ``logmac`` kernels, each inside a ``bench.step`` span.
"""
import json
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def rec():
    return T.Trace.from_json(json.loads(
        (DATA / "batch_decode_trace.json").read_text()))


def _clipped(rec):
    return [(n, max(s, rec.t0), min(e, rec.t1))
            for n, s, e in rec.ops["/device:TPU:0"]
            if min(e, rec.t1) > max(s, rec.t0)]


def test_busy_is_the_union_of_op_intervals(rec):
    # sweep over start/end events: time with at least one op running
    ev = sorted([(s, 1) for _, s, _ in _clipped(rec)]
                + [(e, -1) for _, _, e in _clipped(rec)])
    busy, depth, last = 0.0, 0, None
    for t, d in ev:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert rec.busy_s() == pytest.approx(busy, rel=1e-9)
    assert 0 < rec.busy_s() <= rec.window_s


@pytest.mark.parametrize("kernel", ["logmac", "posit_encode",
                                    "paged_flash_decode"])
def test_kernel_time_sums_its_ops(rec, kernel):
    want = sum(e - s for n, s, e in _clipped(rec)
               if n.startswith(f"%{kernel} "))
    assert want > 0
    assert rec.kernel_s(f"{kernel}$") == pytest.approx(want, rel=1e-9)


def test_idle_gaps_cover_the_idle_time(rec):
    gaps = rec.idle_gaps(10)
    assert sum(t for _, t in gaps) == pytest.approx(
        rec.window_s - rec.busy_s(), rel=1e-6)
    assert {n for n, _ in gaps} <= {"bench.step", "bench.prefill",
                                    "bench.grow", "bench.drive",
                                    "bench.window"}


def test_top_ops_leave_out_control_flow(rec):
    top = dict(rec.top_ops(10))
    assert "while" not in top
    assert max(top, key=top.get) == "logmac"


def test_load_puts_device_ops_inside_their_host_spans():
    import shutil
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        prof = Path(d) / "plugins" / "profile" / "run"
        prof.mkdir(parents=True)
        shutil.copy(DATA / "small.xplane.pb", prof / "host.xplane.pb")
        tr = T.load(d)
    steps = [s for s in tr.spans if s[0] == "bench.step"]
    assert len(steps) == 4
    ops = tr.ops["/device:TPU:0"]
    assert any(T.op_kind(n) == "logmac" for n, _, _ in ops)
    # after the clock shift every op lies in the host span that waited on
    # it (within 1 ms: the launch latency the shift leaves)
    for n, s, e in ops:
        assert any(a - 1e-3 <= s and e <= b + 1e-3 for _, a, b in steps), n
    assert 0 < tr.busy_s() < tr.window_s
