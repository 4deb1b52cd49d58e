"""The harness end to end on the CPU (Pallas interpreted) at a tiny size,
and the command's refusal to run anywhere but on a TPU."""
import io
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

# readings of the tiny cell on the CPU: the program at Posit-16 gaps of
# 0.0035 to 0.0143 over four seeds, the faults of test_output_check 0.76
# to 1.03, the Posit-8 control 0.56
TINY_LIMIT = 0.1


@pytest.fixture(scope="module")
def compiles():
    return harness.Compiles()


def run(compiles, trace=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(tiny.cell(trace, TINY_LIMIT), 2**31 + 11, 2.0,
                           trace, time.perf_counter(), compiles, out=out,
                           err=err, **kw)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res, err.getvalue()


def test_end_to_end_run(compiles):
    res, err = run(compiles)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "check"
    assert "programs compiled or loaded inside the window: 0" in err
    assert err.strip().splitlines()[-1].startswith("logit_gap ")


def test_traced_run_prints_per_layer_metrics(compiles):
    res, _ = run(compiles, trace=True)
    assert res["correct"] is True
    # host-clock and counter metrics exist on the CPU; those against the
    # chip's peaks or its trace are left out there, never reported as 0
    assert {"decode_step_ms", "prefill_share.decode",
            "kv_pages_used_share"} <= set(res["metrics"])
    assert not {"logmac_roofline", "codec_roofline", "decode_mfu",
                "paged_decode_roofline"} & set(res["metrics"])
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "yi-6b-l16.batch_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
