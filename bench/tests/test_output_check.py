"""The output check fails the control and every fault a served cell can
have, at the tiny size on the CPU.  The harness runs whole (chip look
skipped); the timed path is broken underneath the benchmark's stamps."""
import io
import time

import pytest

from bench import harness
from bench.faults import altered_token, half_batch, stale_state
from bench.tests import tiny
from bench.tests.test_cpu_rehearsal import TINY_LIMIT, compiles  # noqa: F401


def correct(compiles, **kw):  # noqa: F811
    res = harness.run_cell(tiny.cell(False, TINY_LIMIT), 2**31 + 12, 2.0,
                           False, time.perf_counter(), compiles,
                           out=io.StringIO(), err=io.StringIO(), **kw)
    return res["correct"], res["check"]["logit_gap"]["value"]


def test_control_fails(compiles):  # noqa: F811
    ok, gap = correct(compiles,
                      posit_width=tiny.CONFIG["serving"]["control_posit_width"])
    assert not ok and gap > TINY_LIMIT


@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token],
                         ids=lambda f: f.__name__)
def test_fault_fails(compiles, fault):  # noqa: F811
    ok, gap = correct(compiles, patch=fault)
    assert not ok and gap > TINY_LIMIT
