#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default (one chip): drives the two hot paths once through the entry
points a user would call, at gpt2-medium's published width and depth
with seeded random weights:

  device  a child asserts platform "tpu" and a device_kind in the peaks
          table; this parent never imports JAX.
  train   ``python -m dlrover_tpu.run --standalone --network-check
          examples/train_transformer.py -- --model gpt2-medium
          --attention splash``: steps with finite non-rising loss, async
          in-memory snapshots plus a persisted save, one SIGKILL from
          here, re-rendezvous, restore from shared memory, compile-cache
          hit, more steps. A second short run covers ``--attention flash``.
  serve   ``examples/serve.py`` answers prompts from the checkpoint train
          wrote (decode_block 1 and the default), then
          ``examples/serve_gateway.py`` answers the same prompts over
          HTTP; greedy tokens must agree.

``--chips 4`` runs only the cross-chip path and its comparison.
``--rehearse`` runs the same phases at the ``tiny`` config with JAX held
to the CPU; it never prints the contract line.

Every phase prints one JSON line; any failure exits non-zero. The last
line of a passing default run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
RUN_TAG = f"chip_smoke_{os.getpid()}"
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")  # small; comes back
WORK_DIR = os.path.join(REPO, ".chip_smoke_work")          # checkpoints; stays
PY = sys.executable


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


# ------------------------------------------------------------ process care


def child_env(cfg, **extra) -> dict:
    env = dict(os.environ)
    env.update({
        "CHIP_SMOKE_RUN": RUN_TAG,  # marks everything we start, for reaping
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "DLROVER_TPU_IPC_DIR": cfg.ipc_dir,
        "DLROVER_TPU_SHM_PREFIX": f"dlrtpu_cs{os.getpid()}",
        "TPU_LOG_DIR": "disabled",
    })
    if cfg.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cfg.chips}")
    env.update(extra)
    return env


def tagged_pids() -> list[int]:
    """Every live process that carries this run's tag in its environment."""
    needle = f"CHIP_SMOKE_RUN={RUN_TAG}".encode()
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


def reap_all() -> None:
    """Stop every process this run started (the launcher's master and
    trainers run in sessions of their own)."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        pids = tagged_pids()
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                pass
        deadline = time.time() + (10 if sig == signal.SIGTERM else 5)
        while time.time() < deadline and tagged_pids():
            time.sleep(0.2)


def live_trainer_pid() -> int | None:
    """The trainer that holds the chip: a tagged process running the
    example that is not a parked standby."""
    for pid in tagged_pids():
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read()
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read()
        except OSError:
            continue
        if b"train_transformer.py" in cmd and b"dlrover_tpu.run" not in cmd \
                and b"DLROVER_TPU_STANDBY_FILE=" not in env:
            return pid
    return None


def run_to_end(cmd: list[str], log: str, env: dict, timeout: float) -> int:
    with open(log, "w") as f:
        proc = subprocess.Popen(cmd, stdout=f, stderr=subprocess.STDOUT,
                                env=env, cwd=REPO)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise PhaseFailed(f"timed out after {timeout}s: {' '.join(cmd)}"
                              f"\n{tail(log)}")


# ------------------------------------------------------------------ phases


DEVICE_CHILD = """
import json, jax
from dlrover_tpu.utils.profiler import PEAKS
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d), "in_peaks_table": d[0].device_kind in PEAKS}))
"""

DEVICE_RE = re.compile(r"devices: platform=(\w+) kind='([^']*)' count=(\d+)")


def device_of(log_text: str) -> dict:
    m = DEVICE_RE.search(log_text)
    check(m is not None, "the entry point printed no device line")
    return {"platform": m.group(1), "device_kind": m.group(2),
            "device_count": int(m.group(3))}


def check_device(cfg, dev: dict, where: str) -> None:
    want = "cpu" if cfg.rehearse else "tpu"
    check(dev["platform"] == want,
          f"{where} ran on platform {dev['platform']!r}, not {want!r}")
    check(dev["device_count"] == cfg.chips,
          f"{where} saw {dev['device_count']} devices, not {cfg.chips}")


def phase_device(cfg) -> dict:
    t0 = time.monotonic()
    out = subprocess.run([PY, "-c", DEVICE_CHILD], env=child_env(cfg),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    check(out.returncode == 0,
          f"device child failed:\n{out.stderr[-3000:]}")
    dev = json.loads(out.stdout.strip().splitlines()[-1])
    if not cfg.rehearse:
        check(dev["platform"] == "tpu",
              f"JAX found no accelerator: platform {dev['platform']!r}")
        check(dev["in_peaks_table"],
              f"device kind {dev['kind']!r} is not in the peaks table")
    else:
        check(dev["platform"] == "cpu", "a rehearsal must run on the CPU")
    check(dev["count"] == cfg.chips,
          f"expected {cfg.chips} device(s), JAX reports {dev['count']}")
    # the agent counts chips without JAX: the sniff must see them too
    from dlrover_tpu.common.accelerator import sniff_accelerator

    kind, count = sniff_accelerator()
    if not cfg.rehearse:
        check(kind == "tpu",
              f"the accelerator sniff does not see the chip JAX sees: "
              f"{(kind, count)}")
    emit({"phase": "device", "platform": dev["platform"],
          "device_kind": dev["kind"], "device_count": dev["count"],
          "sniff": [kind, count], "sniff_count_matches": count == dev["count"],
          "wall_s": round(time.monotonic() - t0, 1)})
    return dev


STEP_RE = re.compile(r"\[trainer\] step (\d+) loss ([-\d.naninf]+)")


def train_cmd(cfg, *, attention: str, ckpt_dir: str, max_steps: int,
              ckpt_interval: int, result_file: str, model: str,
              extra: tuple = ()) -> list[str]:
    if cfg.rehearse:
        # tiny steps take milliseconds: pace them so the kill can land
        shape = ["--model", "tiny", "--attention", "dense",
                 "--step-delay", "0.3"]
    else:
        shape = ["--model", model, "--attention", attention,
                 "--seq", "1024", "--remat", "nothing", "--ce-chunks", "16"]
    return [
        PY, "-m", "dlrover_tpu.run", "--standalone", "--max-restarts", "2",
        "--network-check", "--job-name", "chip-smoke",
        "examples/train_transformer.py", "--", *shape,
        "--global-batch", "8", "--lr", "1e-4", "--dataset-size", "4096",
        "--max-steps", str(max_steps), "--log-interval", "1",
        "--mem-ckpt-interval", "1", "--ckpt-interval", str(ckpt_interval),
        "--ckpt-dir", ckpt_dir, "--result-file", result_file, *extra,
    ]


def wait_for_log(proc, log: str, pattern: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    rx = re.compile(pattern)
    while time.monotonic() < deadline:
        if rx.search(read(log)):
            return
        check(proc.poll() is None,
              f"launcher exited ({proc.returncode}) before "
              f"{pattern!r}:\n{tail(log)}")
        time.sleep(0.1)
    raise PhaseFailed(f"no {pattern!r} within {timeout}s:\n{tail(log)}")


def compile_cache_events(journal_dir: str) -> list[dict]:
    """The journal's ``compile_cache`` events in time order (a crash
    bundle carries a copy of the journal: each event counts once)."""
    events = {}
    for root, _, files in os.walk(journal_dir):
        for name in files:
            if not name.startswith("events"):
                continue
            for line in read(os.path.join(root, name)).splitlines():
                if '"name":"compile_cache"' not in line:
                    continue
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                events[(e.get("t"), e.get("span"))] = e
    return [events[k] for k in sorted(events)]


def losses_ok(steps: list[tuple[int, float]], what: str) -> None:
    check(all(math.isfinite(v) for _, v in steps), f"{what}: loss not finite")
    # seeded random tokens: each batch's loss wobbles around ln(vocab),
    # so compare the ends' means, with room for the wobble
    values = [v for _, v in steps]
    k = min(3, len(values))
    first, last = sum(values[:k]) / k, sum(values[-k:]) / k
    check(last <= first + 0.1,
          f"{what}: loss rose from {first} to {last}")


def kernel_count(cfg, text: str, name: str) -> int:
    """Pallas kernels in the step each incarnation ran, as the trainer
    reports them; on the chip every incarnation must have at least one."""
    kernels = [int(n) for n in
               re.findall(r"(\d+) Pallas custom calls in it", text)]
    if not cfg.rehearse:
        check(kernels and min(kernels) >= 1,
              f"{name}: no tpu_custom_call in the compiled step: {kernels}")
    return kernels[0] if kernels else 0


def run_elastic_train(cfg, *, name: str, attention: str, model: str,
                      max_steps: int, ckpt_interval: int, kill_after: str,
                      min_before: int, min_after: int,
                      extra: tuple = ()) -> tuple[dict, str, str]:
    """One launcher run with a SIGKILL of the trainer in the middle;
    returns (phase record, launcher log, checkpoint dir)."""
    t0 = time.monotonic()
    ckpt_dir = os.path.join(WORK_DIR, f"{name}_ckpt")
    journal = os.path.join(LOG_DIR, f"{name}_journal")
    log = os.path.join(LOG_DIR, f"{name}.log")
    result_file = os.path.join(LOG_DIR, f"{name}_result.json")
    cmd = train_cmd(cfg, attention=attention, ckpt_dir=ckpt_dir,
                    max_steps=max_steps, ckpt_interval=ckpt_interval,
                    result_file=result_file, model=model, extra=extra)
    env = child_env(cfg, DLROVER_TPU_JOURNAL_DIR=journal)
    with open(log, "w") as f:
        proc = subprocess.Popen(cmd, stdout=f, stderr=subprocess.STDOUT,
                                env=env, cwd=REPO)
    try:
        wait_for_log(proc, log, kill_after, cfg.phase_timeout)
        victim = live_trainer_pid()
        check(victim is not None, "no live trainer process to kill")
        os.kill(victim, signal.SIGKILL)
        killed_at = time.monotonic()
        rc = proc.wait(timeout=cfg.phase_timeout)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"{name}: launcher did not finish:\n{tail(log)}")
    finally:
        if proc.poll() is None:
            proc.terminate()
    text = read(log)
    check(rc == 0, f"{name}: launcher exited {rc}:\n{tail(log)}")
    dev = device_of(text)
    check_device(cfg, dev, name)
    resumed = [int(s) for s in
               re.findall(r"\[trainer\] resumed from step (\d+)", text)]
    check(len(resumed) == 1, f"{name}: expected one resume, got {resumed}")
    # the log is in time order: steps before the resume line, then after
    cut = text.index("[trainer] resumed from step")
    before = [(int(s), float(v)) for s, v in STEP_RE.findall(text[:cut])]
    after = [(int(s), float(v)) for s, v in STEP_RE.findall(text[cut:])]
    check(len(before) >= min_before,
          f"{name}: only {len(before)} steps before the kill")
    check(len(after) >= min_after,
          f"{name}: only {len(after)} steps after the resume")
    check(after[0][0] == resumed[0] + 1,
          f"{name}: resumed from {resumed[0]} but next step {after[0][0]}")
    losses_ok(before + after, name)
    result = json.loads(read(result_file))
    check(result["final_step"] == max_steps and result["restart_count"] >= 1,
          f"{name}: result file says {result}")
    events = compile_cache_events(journal)
    hits = [e for e in events if e.get("hit")]
    misses = [e for e in events if not e.get("hit")]
    second = text[text.rindex("[trainer] devices:"):]
    check(bool(events) and events[-1].get("hit")
          and "loaded from compile cache" in second,
          f"{name}: the restart compiled again instead of loading its "
          "executable from the compile cache")
    return {
        "phase": name, **dev, "model": "tiny" if cfg.rehearse else model,
        "attention": "dense" if cfg.rehearse else attention,
        "steps_before_kill": [s for s, _ in before],
        "steps_after_resume": [s for s, _ in after],
        "losses": [v for _, v in before + after],
        "resumed_from": resumed[0],
        "pallas_custom_calls": kernel_count(cfg, text, name),
        "compile_s": round(max((e.get("dur", 0.0) for e in misses),
                               default=0.0), 1),
        "cache_load_s": round(max((e.get("dur", 0.0) for e in hits),
                                  default=0.0), 2),
        "compile_cache_hits": len(hits), "compile_cache_misses": len(misses),
        "kill_to_exit_s": round(time.monotonic() - killed_at, 1),
        "wall_s": round(time.monotonic() - t0, 1),
    }, text, ckpt_dir


def phase_train(cfg) -> str:
    rec, text, ckpt_dir = run_elastic_train(
        cfg, name="train", attention="splash", model="gpt2-medium",
        max_steps=14, ckpt_interval=5,
        kill_after=r"\[trainer\] step 7 loss", min_before=6, min_after=3,
    )
    check(re.search(r"restoring step \d+ from shared memory", text)
          is not None,
          "train: the restart did not restore from shared memory")
    check("persisted step 5" in text or "committed checkpoint step 5" in text,
          "train: step 5 was not persisted")
    if not cfg.rehearse:
        # the async snapshot path is what the CPU never enters
        check("(async writer)" in text,
              "train: no snapshot went through the async writer")
    # the probe child touched the chip and let it go before the trainer
    check("rdzv network-check: round 1 completed" in text,
          "train: the network-check probe did not run")
    rec["restore"] = "shared memory"
    rec["async_snapshots"] = text.count("(async writer)")
    emit(rec)

    # the other library kernel, through the same entry point, no kill
    t0 = time.monotonic()
    log = os.path.join(LOG_DIR, "train_flash.log")
    result_file = os.path.join(LOG_DIR, "train_flash_result.json")
    cmd = train_cmd(cfg, attention="flash", model="gpt2-medium",
                    ckpt_dir=os.path.join(WORK_DIR, "train_flash_ckpt"),
                    max_steps=3, ckpt_interval=100, result_file=result_file)
    rc = run_to_end(cmd, log, child_env(cfg), cfg.phase_timeout)
    text = read(log)
    check(rc == 0, f"train_flash: launcher exited {rc}:\n{tail(log)}")
    dev = device_of(text)
    check_device(cfg, dev, "train_flash")
    steps = [(int(s), float(v)) for s, v in STEP_RE.findall(text)]
    check(len(steps) == 3, f"train_flash: steps {steps}")
    losses_ok(steps, "train_flash")
    emit({"phase": "train_flash", **dev,
          "model": "tiny" if cfg.rehearse else "gpt2-medium",
          "attention": "dense" if cfg.rehearse else "flash",
          "losses": [v for _, v in steps],
          "pallas_custom_calls": kernel_count(cfg, text, "train_flash"),
          "wall_s": round(time.monotonic() - t0, 1)})
    return ckpt_dir


def smoke_prompts(vocab: int) -> list[list[int]]:
    return [
        [5, 9, 2, 7],
        [(i * 37 + 11) % vocab for i in range(100)],  # > one prefill chunk
        [11, 22, 33, 44, 55, 66, 77, 88],
    ]


def serve_once(cfg, model: str, ckpt_dir: str, prompts, block: int | None,
               log: str) -> tuple[list[list[int]], dict]:
    cmd = [PY, "examples/serve.py", "--model", model, "--ckpt-dir", ckpt_dir,
           "--max-new", "16", "--temperature", "0", "--seed", "1"]
    if block is not None:
        cmd += ["--decode-block", str(block)]
    for p in prompts:
        cmd += ["--prompt", " ".join(map(str, p))]
    out = subprocess.run(cmd, env=child_env(cfg), cwd=REPO,
                         capture_output=True, text=True,
                         timeout=cfg.phase_timeout)
    with open(log, "w") as f:
        f.write(out.stdout + "\n--- stderr ---\n" + out.stderr)
    check(out.returncode == 0,
          f"serve.py exited {out.returncode}:\n{out.stderr[-3000:]}")
    check("restored step" in out.stderr,
          "serve.py did not restore the checkpoint")
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    check([r["prompt"] for r in rows] == prompts,
          "serve.py answered other prompts than it was given")
    return [r["tokens"] for r in rows], device_of(out.stderr)


def phase_serve(cfg, ckpt_dir: str) -> None:
    model = "tiny" if cfg.rehearse else "gpt2-medium"
    vocab = 512 if cfg.rehearse else 50257
    prompts = smoke_prompts(vocab)

    t0 = time.monotonic()
    a, dev = serve_once(cfg, model, ckpt_dir, prompts, 1,
                        os.path.join(LOG_DIR, "serve_block1.log"))
    b, _ = serve_once(cfg, model, ckpt_dir, prompts, None,
                      os.path.join(LOG_DIR, "serve_default.log"))
    check_device(cfg, dev, "serve")
    for toks in a + b:
        check(len(toks) == 16 and all(0 <= t < vocab for t in toks),
              f"serve: bad tokens {toks}")
    check(a == b, f"serve: decode_block 1 and the default disagree:\n{a}\n{b}")
    emit({"phase": "serve", **dev, "model": model, "requests": 2 * len(a),
          "tokens": sum(map(len, a + b)), "greedy_tokens_first": a[0],
          "blocks_agree": True, "wall_s": round(time.monotonic() - t0, 1)})

    # the gateway's HTTP door, one replica with its AOT warm-up on
    t0 = time.monotonic()
    log = os.path.join(LOG_DIR, "gateway.log")
    journal = os.path.join(LOG_DIR, "gateway_journal")
    cmd = [PY, "examples/serve_gateway.py", "--model", model,
           "--ckpt-dir", ckpt_dir, "--replicas", "1", "--max-replicas", "1",
           "--host", "127.0.0.1", "--port", "0"]
    with open(log, "w") as f:
        proc = subprocess.Popen(
            cmd, stdout=f, stderr=subprocess.STDOUT, cwd=REPO,
            env=child_env(cfg, DLROVER_TPU_JOURNAL_DIR=journal))
    try:
        wait_for_log(proc, log, r"gateway on http://127\.0\.0\.1:(\d+)",
                     cfg.phase_timeout)
        port = int(re.search(r"gateway on http://127\.0\.0\.1:(\d+)",
                             read(log)).group(1))
        base = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + cfg.phase_timeout
        health = None
        while time.monotonic() < deadline:  # 503 until the replica is READY
            try:
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=10) as r:
                    health = json.loads(r.read())
                    break
            except (urllib.error.HTTPError, urllib.error.URLError, OSError):
                check(proc.poll() is None,
                      f"gateway exited:\n{tail(log)}")
                time.sleep(1.0)
        check(health is not None, f"gateway never healthy:\n{tail(log)}")
        got = []
        for p in prompts:
            req = urllib.request.Request(
                base + "/v1/generate", method="POST",
                data=json.dumps({"prompt": p, "max_new_tokens": 16,
                                 "temperature": 0.0, "seed": 1}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req,
                                        timeout=cfg.phase_timeout) as r:
                check(r.status == 200, f"POST /v1/generate -> {r.status}")
                got.append(json.loads(r.read())["tokens"])
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    text = read(log)
    gdev = device_of(text)
    check_device(cfg, gdev, "gateway")
    check("restored step" in text, "gateway did not restore the checkpoint")
    check(got == a,
          f"gateway and serve.py disagree on greedy tokens:\n{got}\n{a}")
    warm = compile_cache_events(journal)
    check(bool(warm), "gateway: the replica's AOT warm-up left no "
                      "compile_cache event")
    emit({"phase": "gateway", **gdev, "model": model, "requests": len(got),
          "healthz": {k: health.get(k) for k in ("ready", "replicas")
                      if k in health},
          "matches_engine": True,
          "aot_warmup": [{"hit": bool(e.get("hit")),
                          "s": round(e.get("dur", 0.0), 1)} for e in warm],
          "wall_s": round(time.monotonic() - t0, 1)})


# ----------------------------------------------------- four chips (option)


def compare_child(args) -> int:
    """``--child-compare``: gpt2-medium train steps through compile_train
    on ``n`` devices under one strategy; writes losses and where the
    parameter bytes live. Runs in a process of its own (it holds the chips)."""
    import dataclasses

    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel.strategy import PRESETS
    from dlrover_tpu.trainer import bootstrap
    from dlrover_tpu.trainer.train_step import compile_train

    bootstrap.setup_compilation_cache()
    strategy_name, n = args.child_compare.split(":")
    devices = jax.devices()[:int(n)]
    if args.rehearse:
        cfg, seq = tfm.CONFIGS["tiny"], 128
    else:
        cfg, seq = dataclasses.replace(
            tfm.CONFIGS["gpt2-medium"], attention="splash", remat_scan=True,
            remat_policy="nothing", ce_chunks=16), 1024
    strategy = PRESETS[strategy_name]()
    mesh = strategy.build_mesh(devices)
    compiled = compile_train(
        strategy=strategy, mesh=mesh,
        loss_fn=tfm.make_loss_fn(cfg, strategy, mesh),
        init_params_fn=lambda rng: tfm.init_params(cfg, rng),
        logical_params=tfm.logical_axes(cfg),
        optimizer=optax.adamw(1e-4),
    )
    state = compiled.init(jax.random.PRNGKey(0))
    per_device: dict[int, int] = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    rng = np.random.default_rng(0)
    losses = []
    t0 = time.monotonic()
    for _ in range(4):
        tokens = rng.integers(0, cfg.vocab_size, (1, 8, seq + 1),
                              dtype=np.int32)
        batch = jax.device_put({"tokens": tokens}, compiled.batch_sharding)
        state, metrics = compiled.step(state, batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
    text = compiled.step.lower(state, batch).compile().as_text()
    with open(args.out, "w") as f:
        json.dump({
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(jax.devices()), "used_devices": len(devices),
            "strategy": strategy_name, "losses": losses,
            "param_bytes": total,
            "param_bytes_per_device": list(per_device.values()),
            "pallas_custom_calls": text.count("tpu_custom_call"),
            "collectives": {op: text.count(f" {op}(")
                            + text.count(f" {op}-start(")
                            for op in ("all-gather", "reduce-scatter",
                                       "all-reduce")},
            "wall_s": round(time.monotonic() - t0, 1),
        }, f)
    return 0


def phase_four_chips(cfg) -> None:
    runs = {}
    for spec in ("dp:1", f"fsdp:{cfg.chips}"):
        out = os.path.join(LOG_DIR, f"compare_{spec.replace(':', '_')}.json")
        log = os.path.join(LOG_DIR, f"compare_{spec.replace(':', '_')}.log")
        cmd = [PY, os.path.abspath(__file__), "--child-compare", spec,
               "--out", out] + (["--rehearse"] if cfg.rehearse else [])
        rc = run_to_end(cmd, log, child_env(cfg), cfg.phase_timeout)
        check(rc == 0, f"compare {spec} exited {rc}:\n{tail(log)}")
        runs[spec] = json.loads(read(out))
        check_device(cfg, runs[spec], f"compare {spec}")
    one, four = runs["dp:1"], runs[f"fsdp:{cfg.chips}"]
    # bf16 compute under two layouts: reductions reorder, nothing more
    dev = max(abs(a - b) / max(abs(a), 1e-6)
              for a, b in zip(one["losses"], four["losses"]))
    check(dev <= 2e-2, f"dp-on-1 and fsdp-on-{cfg.chips} losses disagree "
                       f"(max rel dev {dev}):\n{one['losses']}\n"
                       f"{four['losses']}")
    share = [b / four["param_bytes"] for b in four["param_bytes_per_device"]]
    check(len(share) == cfg.chips
          and all(abs(s - 1 / cfg.chips) <= 0.05 for s in share),
          f"fsdp parameters are not spread 1/{cfg.chips} per chip: {share}")
    emit({"phase": "compare_dp1_fsdp4", "platform": four["platform"],
          "device_kind": four["device_kind"],
          "device_count": four["device_count"],
          "model": "tiny" if cfg.rehearse else "gpt2-medium",
          "losses_dp1": one["losses"], "losses_fsdp": four["losses"],
          "max_rel_dev": dev, "param_share_per_chip": share,
          "collectives_fsdp": four["collectives"],
          "wall_s": one["wall_s"] + four["wall_s"]})

    # The state is 18.7 GB (f32 params + AdamW), so one sharded save is
    # taken, at step 3 (snapshot to shared memory, persist by the agent —
    # tens of seconds). The kill lands one step later, whatever the disk's
    # speed: shared memory then holds step 3, and three steps remain.
    pace = () if cfg.rehearse else ("--step-delay", "2")
    rec, text, _ = run_elastic_train(
        cfg, name="xl_fsdp", attention="splash", model="gpt2-xl",
        max_steps=6, ckpt_interval=3,
        kill_after=r"\[trainer\] step 4 loss", min_before=3, min_after=2,
        extra=("--strategy", "fsdp", "--sharded-ckpt",
               "--mem-ckpt-interval", "3", *pace),
    )
    check(rec["resumed_from"] == 3,
          f"xl_fsdp: resumed from {rec['resumed_from']}, saved at 3")
    check("committed checkpoint step 3" in text,
          "xl_fsdp: the sharded save of step 3 was never committed")
    rec["strategy"] = "fsdp"
    rec["sharded_ckpt"] = True
    emit(rec)


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser("chip_smoke", description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: only the cross-chip path and its comparison")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny config with JAX held to the CPU")
    p.add_argument("--keep", action="store_true",
                   help="keep the work directory (checkpoints)")
    p.add_argument("--phase-timeout", type=float, default=900.0)
    p.add_argument("--child-compare", default="", help=argparse.SUPPRESS)
    p.add_argument("--out", default="", help=argparse.SUPPRESS)
    cfg = p.parse_args(argv)
    if cfg.child_compare:
        return compare_child(cfg)

    t0 = time.monotonic()
    sys.path.insert(0, REPO)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    os.makedirs(LOG_DIR)
    # AF_UNIX paths are short: the IPC dir goes under TMPDIR, not the checkout
    cfg.ipc_dir = tempfile.mkdtemp(prefix="cs_ipc_")
    ok = False
    try:
        dev = phase_device(cfg)
        if cfg.chips == 4:
            phase_four_chips(cfg)
        else:
            ckpt_dir = phase_train(cfg)
            phase_serve(cfg, ckpt_dir)
        assert "jax" not in sys.modules, "the smoke parent imported JAX"
        ok = True
    except PhaseFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
    finally:
        reap_all()
        shutil.rmtree(cfg.ipc_dir, ignore_errors=True)
        if not cfg.keep:
            shutil.rmtree(WORK_DIR, ignore_errors=True)
        for seg in os.listdir("/dev/shm"):
            if seg.startswith(f"dlrtpu_cs{os.getpid()}"):
                try:
                    os.unlink(os.path.join("/dev/shm", seg))
                except OSError:
                    pass
    if not ok:
        return 1
    print(json.dumps({"total_wall_s": round(time.monotonic() - t0, 1)}),
          flush=True)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    if cfg.rehearse:
        print(json.dumps({"rehearsal_passed": True, "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
