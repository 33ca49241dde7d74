"""Bench harness contract (bench.py): the driver-evidence machinery
that three rounds of rc=124 paid for.

Pins: the hard budget envelope (a stage only starts when the remaining
budget covers its full DEADLINE), the compact headline-only tail line
(parseable from any tail byte-window), atomic emission, and the
SIGTERM flush path.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHeadlineLine:
    def test_headline_line_is_compact_and_parseable(self):
        extra = {
            "ckpt_save_block_s": 0.2, "goodput": 0.97, "mfu": 0.62,
            "mfu_medium": 0.52, "mfu_large": 0.49,
            "ckpt1b_save_block_s": 0.09,
            "serving_toks_per_s": 1000.0, "int8_ffn_speedup": 1.55,
            "lc_best_speedup": 4.2, "bench_total_s": 1500.0,
            "huge_field_that_must_not_leak": "x" * 10000,
        }
        line = bench._headline_line(extra, errors=["e1", "e2"])
        assert len(line) < 1000  # fits ANY tail window
        parsed = json.loads(line)
        assert parsed["metric"] == "ckpt_save_block_s"
        assert parsed["value"] == 0.2
        assert parsed["vs_baseline"] == round(0.5 / 0.2, 2)
        head = parsed["headline"]
        assert head["goodput"] == 0.97
        assert head["mfu_large"] == 0.49
        assert head["n_errors"] == 2
        assert "huge_field_that_must_not_leak" not in head

    def test_every_headline_key_is_known(self):
        """The compact line only carries declared keys — a typo'd key
        would silently vanish from the driver's evidence."""
        for k in bench.HEADLINE_KEYS:
            assert isinstance(k, str) and k

    def test_result_line_roundtrip(self):
        extra = {"ckpt_save_block_s": 0.5, "a": 1}
        parsed = json.loads(bench._result_line(extra))
        assert parsed["vs_baseline"] == 1.0
        assert parsed["extra"]["a"] == 1


class TestBudgetEnvelope:
    def _run_main(self, monkeypatch, budget, stages):
        monkeypatch.setattr(bench, "STAGES", stages)
        monkeypatch.setenv("BENCH_BUDGET_S", str(budget))
        lines = []
        real_write = os.write

        def fake_write(fd, data):
            if fd == 1:
                lines.append(data.decode())
                return len(data)
            return real_write(fd, data)

        monkeypatch.setattr(os, "write", fake_write)
        rc = bench.main()
        return rc, "".join(lines)

    def test_stage_never_starts_without_room_for_its_deadline(
            self, monkeypatch):
        ran = []

        def fast(extra):
            ran.append("fast")

        def never(extra):
            ran.append("never")

        stages = [
            bench.Stage("fast", fast, est_s=1, deadline_s=5),
            # deadline bigger than the whole budget: must be skipped
            bench.Stage("never", never, est_s=1, deadline_s=10_000),
        ]
        rc, out = self._run_main(monkeypatch, budget=60, stages=stages)
        assert rc == 0
        assert ran == ["fast"]
        last = [ln for ln in out.strip().splitlines() if ln][-1]
        parsed = json.loads(last)  # tail line is always parseable
        assert "headline" in parsed

    def test_adaptive_stage_starts_on_min_gate_with_clamped_alarm(
            self, monkeypatch):
        """A min_deadline_s stage starts when the envelope covers only
        its lower gate, and its SIGALRM is clamped to the remaining
        budget (the hard-envelope invariant), not the full deadline."""
        seen = {}

        def adaptive(extra, stage_budget_s=0.0):
            seen["budget"] = stage_budget_s

        stages = [
            bench.Stage("adaptive", adaptive, est_s=1, deadline_s=10_000,
                        pass_budget=True, min_deadline_s=5),
        ]
        rc, out = self._run_main(monkeypatch, budget=60, stages=stages)
        assert rc == 0
        # alarm = min(deadline, left): must be ~the 60 s budget, never
        # the 10_000 s deadline
        assert 5 <= seen["budget"] <= 60

    def test_stage_exception_keeps_run_alive_and_recorded(
            self, monkeypatch):
        def boom(extra):
            raise RuntimeError("stage exploded")

        def fine(extra):
            extra["ckpt_save_block_s"] = 0.1

        stages = [
            bench.Stage("boom", boom, est_s=1, deadline_s=5),
            bench.Stage("fine", fine, est_s=1, deadline_s=5),
        ]
        rc, out = self._run_main(monkeypatch, budget=60, stages=stages)
        assert rc == 1  # the run goes on, but a raised stage is a failure
        lines = [ln for ln in out.strip().splitlines() if ln]
        full = json.loads(lines[-2])
        assert any("stage exploded" in e
                   for e in full["extra"]["errors"])
        assert full["extra"]["ckpt_save_block_s"] == 0.1

    def test_stage_deadline_alarm_bounds_a_wedged_stage(
            self, monkeypatch):
        import time as _time

        def wedge(extra):
            _time.sleep(30)

        stages = [bench.Stage("wedge", wedge, est_s=1, deadline_s=1)]
        t0 = _time.monotonic()
        rc, out = self._run_main(monkeypatch, budget=60, stages=stages)
        assert rc == 1  # a stage that hit its deadline is a failure
        assert _time.monotonic() - t0 < 10
        full = json.loads(
            [ln for ln in out.strip().splitlines() if ln][-2])
        assert any("deadline" in e for e in full["extra"]["errors"])


@pytest.mark.timeout(120)
def test_sigterm_flushes_headline_line(tmp_path):
    """The driver's kill path: SIGTERM mid-run must still leave a
    complete, parseable headline line as the LAST stdout line."""
    script = tmp_path / "driver.py"
    script.write_text(
        "import os, sys, time, signal\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import bench\n"
        "def slow(extra):\n"
        "    extra['ckpt_save_block_s'] = 0.3\n"
        "    bench_pid_file.write_text(str(os.getpid()))\n"
        "    time.sleep(60)\n"
        "from pathlib import Path\n"
        f"bench_pid_file = Path({str(tmp_path / 'pid')!r})\n"
        "bench.STAGES = [bench.Stage('slow', slow, est_s=1,"
        " deadline_s=50)]\n"
        "os.environ['BENCH_BUDGET_S'] = '55'\n"
        "sys.exit(bench.main())\n"
    )
    proc = subprocess.Popen(
        [sys.executable, str(script)], stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
    )
    import time as _time

    pid_file = tmp_path / "pid"
    deadline = _time.monotonic() + 60
    while _time.monotonic() < deadline and not pid_file.exists():
        _time.sleep(0.1)
    assert pid_file.exists()
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode != 0  # termination visible to the driver
    lines = [ln for ln in out.decode().strip().splitlines() if ln]
    parsed = json.loads(lines[-1])
    assert "headline" in parsed
    assert parsed["headline"]["n_errors"] >= 1
    full = json.loads(lines[-2])
    assert any("SIGTERM" in e for e in full["extra"]["errors"])
