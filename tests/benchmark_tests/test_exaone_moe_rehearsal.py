"""``benchmark.run --rehearse`` of the windowed-and-full / shared-expert cell:
the whole harness at the tiny configuration on the CPU, sound and under a
control (a file of its own: a test file runs on one worker, and each case is a
process tree of most of a minute)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "k-exaone-236b-a23b.serve-closed-reasoning"


@pytest.mark.timeout(900)
@pytest.mark.parametrize("control", ["", "chunk_keeps_ring_head"])
def test_the_drivers_rehearsal(control):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("CONTROL", None)
    if control:
        env["CONTROL"] = control
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.stdout.strip(), out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is (control == ""), out.stdout[-2000:]
    assert out.returncode == (0 if control == "" else 1)
    line = last["would_print"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
