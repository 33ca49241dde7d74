"""Driver ``train_launcher``: one elastic training job under the launcher
(``python -m dlrover_tpu.run --standalone examples/train_transformer.py``),
exactly as ``chip_smoke.py`` starts it, measured from the outside through
its goodput log, journal and log. Cells differ only in data:

  job.kill_after_snapshot_steps absent   steady training; the window opens
      at the sync point ``job.window_opens_after_snapshot_steps`` past a
      multiple of the snapshot interval, so that every run holds the same
      snapshots, and ``train_tokens_per_s`` is all its steps over their time.
  job.kill_after_snapshot_steps = n      the window opens at a SIGKILL of the
      live trainer n steps after a committed snapshot and closes when the
      first logged step beyond the killed one has completed in the new
      incarnation: ``resume_s``.
"""

from __future__ import annotations

import json
import math
import os
import re
import signal
import time

from benchmark import goodput_reduce as gr
from benchmark import harness, traffic
from benchmark.harness import BenchFailed, check

SCRIPT = "examples/train_transformer.py"
DEVICE_RE = r"devices: platform=(\w+) kind='([^']*)' count=(\d+)"
REHEARSAL_SHAPE = ["--model", "tiny", "--attention", "dense",
                   "--step-delay", "0.05"]
# at which ``tiny`` falls by 0.26-0.31 between its first and last logged
# losses, as the cell does, and a logged loss swings by 0.003 from batch to
# batch (0.05 at the cell's batch of 8)
REHEARSAL_SEQ, REHEARSAL_BATCH, REHEARSAL_LR = 128, 32, 0.001


def sizes(r: harness.Run, job: dict) -> tuple[int, int, float]:
    """(sequence length, global batch, learning rate) as the job runs."""
    if r.rehearse:
        return REHEARSAL_SEQ, REHEARSAL_BATCH, REHEARSAL_LR
    return job["seq"], job["global_batch"], job["lr"]


def job_command(r: harness.Run, files: dict) -> list[str]:
    job, cfg = r.workload["job"], r.config
    seq, batch, lr = sizes(r, job)
    if r.rehearse:
        shape = REHEARSAL_SHAPE
    else:
        shape = ["--model", cfg["program_model"],
                 "--attention", job["attention"], "--seq", str(seq),
                 "--remat", job["remat"], "--ce-chunks", str(job["ce_chunks"])]
    vocab = 512 if r.rehearse else cfg["vocab_size"]
    windows = int(job["dataset_windows"])
    traffic.token_file(files["data"], vocab, windows * seq + 1, r.seed)
    return [
        harness.PY, "-m", "dlrover_tpu.run", "--standalone",
        "--max-restarts", "2", "--network-check", "--job-name", "benchmark",
        SCRIPT, "--", *shape,
        "--global-batch", str(batch), "--lr", str(lr),
        "--data-file", files["data"], "--max-steps", str(job["max_steps"]),
        "--log-interval", str(job["log_interval"]),
        "--mem-ckpt-interval", str(job["mem_ckpt_interval"]),
        "--ckpt-interval", str(job["ckpt_interval"]),
        "--ckpt-dir", files["ckpt"], "--goodput-log", files["goodput"],
    ]


def last_step(files: dict) -> tuple[int, list[dict]]:
    incs = gr.incarnations(harness.jsonl(files["goodput"]))
    steps = incs[-1]["steps"] if incs else {}
    return (max(steps) if steps else 0), incs


def wait_until(what: str, fn, timeout: float, proc, log: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = fn()
        if got:
            return got
        check(proc.poll() is None,
              f"launcher exited ({proc.returncode}) before {what}:\n"
              f"{harness.tail(log)}")
        time.sleep(0.05)
    raise BenchFailed(f"no {what} within {timeout}s:\n{harness.tail(log)}")


def capture_profile(r: harness.Run, files: dict, steps: int, proc) -> str:
    """Arm the trainer's own on-demand profiler capture (the request file
    its step loop looks for) and wait for the bundle; returns its path."""
    root = os.path.join(files["journal"], "bundles")
    os.makedirs(root, exist_ok=True)
    before = set(os.listdir(root))
    req = os.path.join(root, "profile_request_node0.json")
    with open(req + ".tmp", "w") as f:
        json.dump({"steps": steps, "id": r.tag, "t": time.time()}, f)
    os.replace(req + ".tmp", req)

    def done():
        for name in sorted(set(os.listdir(root)) - before):
            if "_profile_" in name and os.path.isfile(
                    os.path.join(root, name, "manifest.json")):
                return os.path.join(root, name)
        return None

    return wait_until("the profile bundle", done, 120, proc, files["log"])


def bundle_memory_peak(bundle: str) -> int:
    devices = harness.load_json(os.path.join(bundle, "manifest.json")).get(
        "devices") or []
    return max((int((d.get("memory_stats") or {}).get("peak_bytes_in_use", 0))
                for d in devices), default=0)


def reduce_trace(r: harness.Run, bundle: str) -> dict:
    """The bundle's xplane file, reduced in a child that holds no chip."""
    return r.child_json(
        [harness.PY, "-m", "benchmark.trace_reduce", bundle,
         r.path("trace.json")], r.path("trace_reduce.log"),
        r.path("trace.json"), 300, JAX_PLATFORMS="cpu")


def run(r: harness.Run) -> dict:
    job = r.workload["job"]
    interval, cycle = int(job["log_interval"]), int(job["mem_ckpt_interval"])
    files = {k: r.path(v) for k, v in {
        "data": "tokens.bin", "ckpt": "ckpt", "goodput": "goodput.jsonl",
        "journal": "journal", "log": "launcher.log"}.items()}
    proc = r.start(job_command(r, files), files["log"],
                   DLROVER_TPU_JOURNAL_DIR=files["journal"])
    log = files["log"]
    m = r.wait_for(proc, log, DEVICE_RE, 300)
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    check(device["platform"] == ("cpu" if r.rehearse else "tpu"),
          f"the trainer runs on {device['platform']!r}: no accelerator")
    check(device["count"] == r.cell["chips"],
          f"the trainer sees {device['count']} chips, the cell asks for "
          f"{r.cell['chips']}")
    # set-up: the compile or cache load, then the first snapshot of the
    # process, which costs several times a later one
    first = r.wait_for(proc, log, gr.SNAPSHOT_RE.pattern, 900)
    out = {"device": device, "files": files, "job": job, "config": r.config,
           "log_interval": interval, "cycle": cycle, "kill_t": None}
    kill_after = job.get("kill_after_snapshot_steps")
    if kill_after is None:
        measure_steady(r, out, proc, first)
    else:
        measure_kill(r, out, proc, first, int(kill_after))
    # the trainer's own capture: the trace in a traced run, and in every
    # run the only place the chip's holder reports its memory
    bundle = capture_profile(r, files, int(job["trace_steps"]) if r.trace
                             else 1, proc)
    device["memory_peak_bytes"] = bundle_memory_peak(bundle)
    r.reap()  # the job has no end of its own inside a run
    out["log_text"] = harness.read(log)
    out["goodput"] = harness.jsonl(files["goodput"])
    if r.trace:
        out["trace"] = reduce_trace(r, bundle)
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
    check_job(r, out)
    return out


def measure_steady(r, out, proc, first) -> None:
    files, interval, cycle = out["files"], out["log_interval"], out["cycle"]
    phase = int(out["job"]["window_opens_after_snapshot_steps"]) % cycle

    def opening():
        # the first sync point at the window's place in the snapshot cycle
        # after the first snapshot has landed: a request (every ``cycle``
        # steps) slows the two sync intervals after it, so a window that
        # opens past those holds the same number of stalls in every run
        incs = last_step(files)[1]
        syncs = gr.sync_points(incs[-1]["steps"], interval) if incs else {}
        later = [s for s in syncs if s % cycle == phase
                 and syncs[s] >= landed]
        return (min(later), syncs[min(later)]) if later else None

    landed = time.time()
    s0, t0 = wait_until("the window's sync point after the first snapshot",
                        opening, 180, proc, files["log"])
    time.sleep(max(0.0, t0 + r.seconds - time.time()))
    _, incs = last_step(files)
    check(len(incs) == 1, "the trainer restarted inside a steady window")
    syncs = gr.sync_points(incs[0]["steps"], interval)
    # all the work of the window over all its time, as far as the host's
    # clock can stand behind it: from its first to its last sync point
    n_steps, took = gr.window_steps(syncs, s0, t0 + r.seconds)
    check(n_steps >= cycle, f"no snapshot cycle of {cycle} steps fits into "
          f"{r.seconds}s")
    seq, batch, _ = sizes(r, out["job"])
    tokens_per_step = batch * seq
    out.update({
        "window": (t0, t0 + took), "first_sync": s0, "steps": n_steps,
        "tokens_per_step": tokens_per_step,
        "attempted": n_steps, "failed": 0,
        "e2e": {"train_tokens_per_s": n_steps * tokens_per_step / took,
                "setup_s": t0 - r.t_start},
    })


def measure_kill(r, out, proc, first, kill_after: int) -> None:
    files, interval = out["files"], out["log_interval"]
    log = files["log"]
    # a snapshot later than the process's first, committed to shared memory
    snap = r.wait_for(proc, log, gr.SNAPSHOT_RE.pattern, 300,
                      start=first.end())
    at = int(snap.group(1))
    wait_until(f"step {at + kill_after}",
               lambda: last_step(files)[0] >= at + kill_after, 120, proc, log)
    victim = r.live_trainer_pid(SCRIPT)
    check(victim is not None, "no live trainer process to kill")
    t_kill = time.time()
    os.kill(victim, signal.SIGKILL)
    time.sleep(0.2)
    killed_step = last_step(files)[0]
    beyond = (killed_step // interval + 1) * interval

    def resumed():
        _, incs = last_step(files)
        if len(incs) < 2:
            return None
        return gr.sync_points(incs[-1]["steps"], interval).get(beyond)

    # one recovery is a fixed amount of work, not a rate: the window closes
    # when it is done, and the run fails where that takes longer than it
    t_done = wait_until(f"step {beyond} of the new incarnation", resumed,
                        max(r.seconds - (time.time() - t_kill), 0.0), proc,
                        log)
    incs = last_step(files)[1]
    out.update({
        "window": (t_kill, t_done), "kill_t": t_kill,
        "snapshot_step": at, "killed_step": killed_step,
        "resumed_to_step": beyond,
        "redone_steps": beyond - min(incs[-1]["steps"]) + 1,
        "attempted": 1, "failed": 0,
        "e2e": {"resume_s": t_done - t_kill, "setup_s": t_kill - r.t_start},
    })


def logged_loss_checks(log_text: str, limits: dict) -> list[dict]:
    """The timed job's own losses, as it logged them at its sync points:
    finite, and the mean of the last three below the mean of the first
    three by at least the limit (which is negative: a job whose step
    returns its state unchanged reads about 0 and fails)."""
    values = [v for _, v in gr.logged_losses(log_text)]
    finite = all(math.isfinite(v) for v in values) and len(values) >= 2
    k = min(3, len(values))
    rise = (sum(values[-k:]) - sum(values[:k])) / k if finite else math.inf
    return [{"name": "logged_losses_not_finite", "value": 0 if finite else 1,
             "limit": 0},
            {"name": "logged_loss_rise", "value": rise,
             "limit": limits["logged_loss_rise"]}]


def check_job(r: harness.Run, out: dict) -> None:
    """`correct`. Of the timed job itself only its logged losses are
    checked: they cover the optimizer, donation and the batches it trained
    on, coarsely. The loss and gradient comparison with the plain reference
    is made on a program of the check's own: the program's loss function
    built as the job builds it, at the cell's sizes, in a child that takes
    the chip once the job has released it. It is not the compiled step the
    window drove: the trainer hands out no state at steps 1-3 (PERF.md,
    Open questions)."""
    limits = r.workload["limits"]
    checks = logged_loss_checks(out["log_text"], limits)
    if out["kill_t"] is not None:
        # the restore must hand back the committed snapshot: the step it
        # names, and a loss that goes on from where the killed one was (a
        # lost or stale state shows as a jump back towards ln(vocab) + 0.5).
        # Redone steps do NOT repeat the first incarnation's losses: the
        # master hands the shards in flight at the kill out again in
        # another order, so each sample is seen once, not each batch twice.
        m = re.search(r"\[trainer\] resumed from step (\d+)", out["log_text"])
        resumed = int(m.group(1)) if m else -1
        cut = out["log_text"].index(m.group(0)) if m else 0
        before = [v for _, v in gr.logged_losses(out["log_text"][:cut])][-2:]
        after = [v for _, v in gr.logged_losses(out["log_text"][cut:])][:2]
        rise = (sum(after) / len(after) - sum(before) / len(before)
                if before and after else math.inf)
        checks.append({"name": "resumed_from_gap", "limit": 0,
                       "value": abs(resumed - out["snapshot_step"])})
        checks.append({"name": "resume_loss_rise", "value": rise,
                       "limit": limits["resume_loss_rise"]})
    cmd = [harness.PY, "-m", "benchmark.check_train",
           "--workload", r.cell["name"], "--seed", str(r.seed),
           "--out", r.path("check.json")]
    if r.rehearse:
        cmd.append("--rehearse")
    t0 = time.time()
    verdict = r.child_json(cmd, r.path("check.log"), r.path("check.json"), 600)
    check(verdict["device"]["platform"] == out["device"]["platform"],
          "the check ran on another platform than the job")
    checks += verdict["checks"]
    out["check_seconds"] = time.time() - t0
    for c in checks:
        c["ok"] = bool(c["value"] <= c["limit"])
    out["checks"] = checks
    out["correct"] = all(c["ok"] for c in checks)
