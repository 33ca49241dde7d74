"""What every driver needs and none should reinvent: the run's paths and
environment, tagged child processes and their reaping, waiting on a log.
Lifted from ``chip_smoke.py``, which already launches, kills, reaps and
reads this system on the chip. Never imports JAX: the children hold the chip.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
PY = sys.executable


class BenchFailed(RuntimeError):
    """The run cannot give a result: no result line, exit code 1."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailed(what)


def read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def tail(path: str, n: int = 3000) -> str:
    return read(path)[-n:]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_named(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``, found by the name alone
    (names may hold dots and dashes, so not through ``import``)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    check(os.path.isfile(path), f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Run:
    """One invocation: which cell, its data files, where it may write."""
    cell: dict            # the BENCHMARK.json workloads entry
    workload: dict        # benchmark/workloads/<cell>.json
    config: dict          # benchmark/configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float        # time.time() at process start: set-up counts from here
    root: str = ROOT

    def __post_init__(self):
        self.tag = f"bench_{os.getpid()}"
        self.shm_prefix = f"dlrtpu_bm{os.getpid()}"
        # everything a run writes stays in the checkout (git-ignored) ...
        self.work = os.path.join(self.root, ".benchmark_work",
                                 f"{self.cell['name']}-{os.getpid()}")
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        # ... except the unix sockets: AF_UNIX paths are short, so under TMPDIR
        self.ipc_dir = tempfile.mkdtemp(prefix="bm_ipc_")

    def path(self, *parts: str) -> str:
        return os.path.join(self.work, *parts)

    def env(self, **extra: str) -> dict:
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        env.update({
            "BENCHMARK_RUN_TAG": self.tag,  # marks what we start, for reaping
            "PYTHONPATH": self.root + os.pathsep + env.get("PYTHONPATH", ""),
            "DLROVER_TPU_IPC_DIR": self.ipc_dir,
            "DLROVER_TPU_SHM_PREFIX": self.shm_prefix,
            "TPU_LOG_DIR": "disabled",
        })
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env.update(extra)
        return env

    # -------------------------------------------------------- process care

    def tagged_pids(self) -> list[int]:
        needle = f"BENCHMARK_RUN_TAG={self.tag}".encode()
        found = []
        for name in os.listdir("/proc"):
            if not name.isdigit() or int(name) == os.getpid():
                continue
            try:
                with open(f"/proc/{name}/environ", "rb") as f:
                    if needle in f.read():
                        found.append(int(name))
            except OSError:
                continue
        return found

    def live_trainer_pid(self, script: str) -> int | None:
        """The tagged process that runs ``script`` and holds the chip:
        not the launcher, not a parked standby."""
        for pid in self.tagged_pids():
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read()
                with open(f"/proc/{pid}/environ", "rb") as f:
                    env = f.read()
            except OSError:
                continue
            if script.encode() in cmd and b"dlrover_tpu.run" not in cmd \
                    and b"DLROVER_TPU_STANDBY_FILE=" not in env:
                return pid
        return None

    def reap(self) -> None:
        """Stop every process this run started and wait until each has
        ended (the launcher's master and trainers run in their own
        sessions); then drop shared memory, sockets and the work files."""
        for sig in (signal.SIGTERM, signal.SIGKILL):
            pids = self.tagged_pids()
            if not pids:
                break
            for pid in pids:
                try:
                    os.kill(pid, sig)
                except ProcessLookupError:
                    pass
            deadline = time.time() + (10 if sig == signal.SIGTERM else 5)
            while time.time() < deadline and self.tagged_pids():
                time.sleep(0.1)
        for seg in os.listdir("/dev/shm"):
            if seg.startswith(self.shm_prefix):
                try:
                    os.unlink(os.path.join("/dev/shm", seg))
                except OSError:
                    pass
        shutil.rmtree(self.ipc_dir, ignore_errors=True)

    def cleanup(self, keep: tuple[str, ...] = ()) -> None:
        """Delete the work directory but for the small files in ``keep``."""
        for name in os.listdir(self.work):
            if name in keep:
                continue
            full = self.path(name)
            if os.path.isdir(full):
                shutil.rmtree(full, ignore_errors=True)
            else:
                os.unlink(full)

    def start(self, cmd: list[str], log: str, **extra_env: str):
        f = open(log, "w")
        try:
            return subprocess.Popen(cmd, stdout=f, stderr=subprocess.STDOUT,
                                    env=self.env(**extra_env), cwd=self.root)
        finally:
            f.close()

    def wait_for(self, proc, log: str, pattern: str, timeout: float,
                 start: int = 0) -> re.Match:
        """The first match of ``pattern`` in ``log`` after offset ``start``."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = rx.search(read(log), start)
            if m:
                return m
            check(proc.poll() is None, f"process exited ({proc.returncode}) "
                  f"before {pattern!r}:\n{tail(log)}")
            time.sleep(0.05)
        raise BenchFailed(f"no {pattern!r} within {timeout}s:\n{tail(log)}")

    def child_json(self, cmd: list[str], log: str, out: str,
                   timeout: float, **extra_env: str) -> dict:
        """Run a child to its end; it writes its answer as JSON to ``out``."""
        proc = self.start(cmd, log, **extra_env)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise BenchFailed(f"timed out after {timeout}s: {' '.join(cmd)}"
                              f"\n{tail(log)}") from None
        check(rc == 0 and os.path.isfile(out),
              f"{' '.join(cmd[:4])} ... exited {rc}:\n{tail(log)}")
        return load_json(out)


def jsonl(path: str) -> list[dict]:
    """The whole lines of a JSON-lines file (a SIGKILL tears the last)."""
    out = []
    for line in read(path).splitlines():
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if isinstance(ev, dict):
            out.append(ev)
    return out


def journal_events(journal_dir: str, names: tuple[str, ...]) -> list[dict]:
    """The journal's events of these names in time order (a crash bundle
    carries a copy of the journal: each event counts once)."""
    events = {}
    for base, _, files in os.walk(journal_dir):
        for name in files:
            if not name.startswith(("events", "journal_tail")):
                continue
            for e in jsonl(os.path.join(base, name)):
                if e.get("name") in names:
                    events[(e.get("t"), e.get("span"))] = e
    return [events[k] for k in sorted(events, key=lambda k: (k[0] or 0.0,
                                                            str(k[1])))]
