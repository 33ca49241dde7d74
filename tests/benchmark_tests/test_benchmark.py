"""The benchmark's own tests: CPU, tiny sizes, one file.

What is checked here never needs the chip: that ``BENCHMARK.json`` and the
data files agree, that traffic repeats for a seed, the reductions from a
recorded goodput log and a recorded trace, the reference against the
program's model on ``tiny``, the counts against hand values, that nothing is
printed without a chip, that a lower-precision control and a broken timed
path both come out as not correct, and that a later PR can add a
configuration, a cell and a per-layer metric as files alone.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import goodput_reduce as gr  # noqa: E402
from benchmark import harness, trace_reduce, traffic  # noqa: E402
from benchmark.counts import flops, peaks, splash  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[0-9A-Za-z_][0-9A-Za-z_.\-]{0,63}$")
UNIT = re.compile(r"[0-9A-Za-z_/%.\-]{1,16}$")


def workload_file(cell: str) -> dict:
    return harness.load_json(
        os.path.join(ROOT, "benchmark", "workloads", f"{cell}.json"))


# ------------------------------------------------------------ the contract


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                          "device_trace")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_agrees_with_its_files(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    wl = workload_file(cell)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert cell == f"{entry['config']}.{entry['traffic']}"
    cfg = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "drivers", f"{wl['driver']}.py"))
    reports = [m for m in BENCH["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
    assert {"setup_s"} < {m["name"] for m in reports}
    layers = [m for m in BENCH["per_layer"] if cell in m.get("workloads", [cell])]
    assert layers
    for m in layers:  # every cell that reports it reports what it moves
        assert m["moves"] in {e["name"] for e in reports}
        harness.load_named("layer_metrics", m["name"]).read  # found by name


def test_names_units_and_lengths():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        assert "unit" not in e or UNIT.match(e["unit"]), e
        assert "better" not in e or e["better"] in ("lower", "higher")
        assert all(1 <= len(e[k]) <= 200 and "\n" not in e[k]
                   for k in ("why", "layer", "source") if k in e)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for base, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            assert re.fullmatch(r"[0-9A-Za-z_.\-]+", f), f
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_declared_layer_metric_has_its_file():
    # files of cells that were put off (PERF.md, Open questions) may wait
    have = {f[:-3] for f in os.listdir(os.path.join(
        ROOT, "benchmark", "layer_metrics"))
        if f.endswith(".py") and f != "__init__.py"}
    assert {m["name"] for m in BENCH["per_layer"]} <= have


# ----------------------------------------------------------------- traffic


@pytest.mark.parametrize("cell", ["gpt2-medium.serve-closed",
                                  "gpt2-medium.serve-rate80"])
def test_traffic_repeats_for_a_seed_and_differs_between_seeds(cell):
    mix = workload_file(cell)["traffic_mix"]
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 40.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 40.0)
    assert a == b and a != c
    assert traffic.prompt_ids(a[0], 50257) == traffic.prompt_ids(b[0], 50257)
    assert traffic.prompt_ids(a[0], 50257) != traffic.prompt_ids(c[0], 50257)
    for r in a:
        assert 16 <= r.prompt_tokens <= 768 and 16 <= r.max_new_tokens <= 256
        assert r.prompt_tokens + r.max_new_tokens <= 1024
    if mix["arrivals"]["kind"] == "closed":
        # every seed offers the same set of sizes, in another order
        assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
            sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    else:
        dues = [r.due_s for r in a]
        assert dues == sorted(dues) and dues[-1] < 40.0
        rate = len(a) / 40.0
        assert 0.6 * mix["arrivals"]["rate_per_s"] < rate \
            < 1.4 * mix["arrivals"]["rate_per_s"]


def test_token_file_is_seeded(tmp_path):
    paths = [str(tmp_path / n) for n in "abc"]
    for path, seed in zip(paths, (7, 7, 8)):
        traffic.token_file(path, 512, 1000, seed)
    a, b, c = (np.fromfile(p, np.uint32) for p in paths)
    assert a.size == 1000 and (a == b).all() and (a != c).any()
    assert a.max() < 512


# ------------------------------------------------- goodput-log reductions


@pytest.fixture(scope="module")
def recorded():
    """A recorded run of the kill-resume cell at the tiny config (CPU)."""
    events = harness.jsonl(os.path.join(DATA, "goodput_kill.jsonl"))
    log = harness.read(os.path.join(DATA, "launcher_kill.log"))
    return events, log


def test_sync_points_and_the_windows_steps(recorded):
    events, _ = recorded
    incs = gr.incarnations(events)
    assert len(incs) == 2 and incs[1]["restart"] == 1
    steps = incs[0]["steps"]
    syncs = gr.sync_points(steps, 10)
    assert set(syncs) <= {s for s in steps if s % 10 == 0}
    assert all(syncs[s] == steps[s + 1] for s in syncs)
    first = min(syncs)
    # all the steps between the window's first and last sync point count
    n, took = gr.window_steps(syncs, first, syncs[first] + 1e9)
    assert n == max(syncs) - first and n >= 40
    assert took == pytest.approx(syncs[max(syncs)] - syncs[first])
    # a window that ends between two sync points counts up to the earlier
    n2, took2 = gr.window_steps(syncs, first, syncs[first + 50] - 1e-3)
    assert n2 == 40 and took2 == pytest.approx(syncs[first + 40]
                                                - syncs[first])
    assert gr.window_steps(syncs, first, syncs[first]) == (0, 0.0)


def test_tokens_per_second_and_stall_from_the_log(recorded):
    events, log = recorded
    steps = gr.incarnations(events)[0]["steps"]
    syncs = gr.sync_points(steps, 10)
    first = min(syncs)
    n, took = gr.window_steps(syncs, first, syncs[first] + 1e9)
    run = {"goodput": events, "log_text": log, "log_interval": 10,
           "cycle": 40, "first_sync": first, "steps": n, "kill_t": None,
           "window": (syncs[first], syncs[first] + took)}
    step_ms = harness.load_named("layer_metrics", "step_ms").read(run)
    stall = harness.load_named("layer_metrics", "snapshot_stall_ms").read(run)
    assert 50 < step_ms < 200          # the recording paced steps at ~85 ms
    whole = n // 40 * 40
    mean_ms = 1e3 * (syncs[first + whole] - syncs[first]) / whole
    assert stall == pytest.approx(40 * (mean_ms - step_ms), abs=40 * 25)
    snaps = gr.snapshots(log)
    assert snaps and all(s % 40 == 0 for s, _ in snaps)


def test_resume_and_respawn_from_the_log(recorded):
    events, log = recorded
    incs = gr.incarnations(events)
    killed = max(incs[0]["steps"])
    kill_t = incs[0]["steps"][killed] + 0.01
    beyond = (killed // 10 + 1) * 10
    done = gr.sync_points(incs[1]["steps"], 10)[beyond]
    run = {"goodput": events, "kill_t": kill_t}
    respawn = harness.load_named("layer_metrics", "respawn_s").read(run)
    assert respawn == pytest.approx(incs[1]["start_t"] - kill_t)
    assert 0 < respawn < done - kill_t
    losses = gr.logged_losses(log)
    assert len(losses) > 4 and all(np.isfinite(v) for _, v in losses)
    # steady cells have nothing to read for a kill metric: left out
    assert harness.load_named("layer_metrics", "respawn_s").read(
        {"goodput": events, "kill_t": None}) is None


# ---------------------------------------------------------- trace reduction


def test_trace_reduction_on_a_recorded_trace():
    rec = harness.load_json(os.path.join(DATA, "trace_events.json"))
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    out = trace_reduce.reduce(devices, tuple(rec["extent_ns"]))
    assert out["devices"] == len(devices)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    top = out["breakdown"]["device_ops"]
    assert len(top) <= 10 and top == sorted(top, key=lambda t: -t[1])
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    # self time: a while does not count its body twice
    total_self = sum(op["self_s"] for op in out["ops"].values())
    assert total_self == pytest.approx(out["busy_s"], rel=1e-6)


def test_busy_union_and_self_time_by_hand():
    ev = [("while", 0, 100), ("fusion", 10, 30), ("kernel", 50, 40),
          ("copy", 150, 50)]
    busy, gaps = trace_reduce.busy_union(ev)
    assert busy == 150 and gaps == [(50, "while", "copy")]
    own = trace_reduce.self_times(ev)
    assert own == {"while": [30, 1], "fusion": [30, 1], "kernel": [40, 1],
                   "copy": [50, 1]}


# ------------------------------------------------------------------ counts


def test_flop_and_byte_counts_by_hand():
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "gpt2-medium.json"))
    # 24 layers of 4*1024^2 + 2*1024*4096, and a 1024 x 50257 head
    assert flops.matmul_params(cfg) == 24 * 12582912 + 51463168
    fwd = flops.forward_flops_per_token(cfg, 1024)
    assert fwd == 2 * 353453056 + 24 * 4 * 1024 * 1025 / 2
    assert flops.train_flops_per_token(cfg, 1024) == 3 * fwd
    call = splash.forward_call(8, 16, 1024, 64)
    assert call["flops"] == 4 * 8 * 16 * (1024 * 1025 / 2) * 64
    assert call["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2 + 8 * 16 * 1024 * 4
    assert splash.backward_call(8, 16, 1024, 64)["flops"] == 2.5 * call["flops"]
    secs, bound = splash.least_seconds(call, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and secs == pytest.approx(call["flops"] / 197e12)
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


# --------------------------------------------------------------- reference


@pytest.fixture(scope="module")
def tiny():
    import jax

    from benchmark.program import tiny_config
    from benchmark.reference import gpt2
    from dlrover_tpu.models import transformer as tfm

    cfg = tiny_config()
    pcfg = dataclasses.replace(tfm.CONFIGS["tiny"], variant="gpt2",
                               dtype="float32")
    params = gpt2.init_params(cfg, 2**31 + 5)
    tokens = np.asarray(traffic.rng_for(3).integers(0, 512, (4, 65)),
                        np.int32)
    return jax, gpt2, tfm, cfg, pcfg, params, tokens


def test_reference_weights_have_the_programs_layout(tiny):
    jax, gpt2, tfm, cfg, pcfg, params, _ = tiny
    theirs = tfm.init_params(pcfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, params) == \
        jax.tree.map(lambda a: a.shape, theirs)
    again = gpt2.init_params(cfg, 2**31 + 5)
    other = gpt2.init_params(cfg, 2**31 + 6)
    assert (np.asarray(params["embed"]) == np.asarray(again["embed"])).all()
    assert (np.asarray(params["embed"]) != np.asarray(other["embed"])).any()


def test_reference_agrees_with_the_programs_model_on_tiny(tiny):
    jax, gpt2, tfm, cfg, pcfg, params, tokens = tiny
    with jax.default_matmul_precision("highest"):
        theirs = tfm.forward(params, tokens[:, :-1], pcfg)
        their_loss = tfm.loss_fn(params, {"tokens": tokens}, pcfg)
    ours = gpt2.logits(params, tokens[:, :-1])
    # float32 on both sides: rounding of a different operation order only
    assert float(np.abs(np.asarray(ours) - np.asarray(theirs)).max()) < 2e-4
    assert abs(float(gpt2.loss(params, tokens)) - float(their_loss)) < 1e-5


def test_lower_precision_control_and_dropped_rows_fail_the_training_limits(tiny):
    """The control (the reference at the precision below the program's,
    in the program's place) and a batch with a part left out must fail the
    cell's own limits; the float32 reference against itself passes."""
    jax, gpt2, tfm, cfg, pcfg, params, tokens = tiny
    limits = workload_file("gpt2-medium.train-steady")["limits"]
    loss_r, grads_r = gpt2.loss_and_grads(params, tokens, rows=2)
    norms_r = gpt2.leaf_norms(grads_r)

    def numbers(loss, grads):
        return abs(loss - loss_r), gpt2.norm_gap(gpt2.leaf_norms(grads),
                                                 norms_r)

    same = numbers(*gpt2.loss_and_grads(params, tokens, rows=4))
    assert same[0] <= limits["loss_gap"] / 10
    assert same[1] <= limits["grad_norm_gap"] / 10
    fp8 = numbers(*gpt2.loss_and_grads(params, tokens, rows=2,
                                       precision="fp8"))
    assert fp8[1] > limits["grad_norm_gap"] or fp8[0] > limits["loss_gap"]
    half = numbers(*gpt2.loss_and_grads(params, tokens[:2], rows=2))
    assert half[1] > limits["grad_norm_gap"] or half[0] > limits["loss_gap"]


# --------------------------------------------- a run with the chip look skipped


def serve_spec(tmp_path, seconds=3.0) -> dict:
    from benchmark.program import tiny_config
    from benchmark.drivers import serve_gateway as drv

    wl = workload_file("gpt2-medium.serve-closed")
    mix = dict(wl["traffic_mix"], **drv.REHEARSAL_LENGTHS)
    return {"seed": 2**31 + 9, "seconds": seconds, "trace": False,
            "rehearse": True, "chips": 1, "config": tiny_config(),
            "serving": drv.REHEARSAL_SERVING, "traffic": mix,
            "limits": wl["limits"], "sample": wl["sample"],
            "trace_dir": str(tmp_path / "trace"), "t_start": 0.0,
            "trace_after_s": 1, "trace_seconds": 1}


@pytest.mark.parametrize("broken", [False, True])
def test_serving_run_is_correct_unless_the_timed_path_is_broken(
        tmp_path, broken):
    """Drives the serving child's own set-up, window and check at the tiny
    config (the look for a chip skipped). With the engine's weights spoiled
    underneath the gateway, the tokens it produces are another model's, and
    `correct` must come out false."""
    import time

    import jax

    from benchmark import serve_child as sc

    from benchmark.reference import gpt2

    spec = serve_spec(tmp_path)
    params = gpt2.init_params(spec["config"], spec["seed"])
    device, pcfg, gateway = sc.build(spec)
    try:
        replica = gateway.pool.ready_replicas()[0]
        if broken:
            replica.engine.params = jax.tree.map(
                lambda a: a * 1.5 if a.ndim == 4 else a, params)
        sc.warm_up(gateway, spec, pcfg.vocab_size)
        now = time.monotonic()
        window = sc.drive(gateway, spec, pcfg.vocab_size, now, now)
        summary = sc.summarize(window, now, spec["seconds"])
        prefill, sample = sc.sample_and_prefill(spec, replica.engine,
                                                window, "")
    finally:
        gateway.stop()
    checks = sc.reference_checks(spec, params, prefill, sample, "")
    assert summary["failed"] == 0 and summary["serve_tokens_per_s"] > 0
    assert {c["name"] for c in checks} == {"decode_logit_gap",
                                           "prefill_logit_gap"}
    correct = all(c["value"] <= c["limit"] for c in checks)
    assert correct is (not broken), checks
    if not broken:
        # the control: the reference at fp8 in the program's place must
        # put tokens first that the float32 reference rules out
        control = sc.reference_checks(spec, params, {}, sample, "fp8")
        assert control[0]["value"] > 3 * max(checks[0]["value"], 1e-3)


@pytest.mark.parametrize("broken", [False, True])
def test_training_run_is_correct_unless_the_step_leaves_its_state_unchanged(
        monkeypatch, broken):
    """Drives the training driver's own set-up, window and check at the tiny
    config under the real launcher (the look for a chip skipped). With the
    timed job's update planted as a no-op (a learning rate of 0: every step
    hands back the parameters it was given), its logged losses do not fall
    and `correct` must come out false on `logged_loss_rise` alone."""
    import time

    from benchmark.drivers import train_launcher as drv

    cell = "gpt2-medium.train-steady"
    if broken:
        sound_command = drv.job_command

        def no_op_update(r, files):
            cmd = sound_command(r, files)
            cmd[cmd.index("--lr") + 1] = "0"
            return cmd

        monkeypatch.setattr(drv, "job_command", no_op_update)
    # Other test workers end their chaos scenarios with a machine-wide
    # `pkill -9 -f` of the example script and of the job master
    # (dlrover_tpu/chaos/scenario.py), which takes this job with them: a run
    # that could give no result is made again, a wrong result never is.
    for attempt in range(3):
        r = harness.Run(
            cell=next(w for w in BENCH["workloads"] if w["name"] == cell),
            workload=workload_file(cell), config={}, seed=2**31 + 77,
            seconds=24.0, trace=False, rehearse=True, t_start=time.time())
        # (24 s: beside five other test workers a tiny step takes up to
        # 0.4 s, and the window has to hold a snapshot cycle of 40)
        try:
            out = drv.run(r)
            break
        except harness.BenchFailed:
            if attempt == 2:
                raise
        finally:
            r.reap()
            shutil.rmtree(r.work, ignore_errors=True)
    checks = {c["name"]: c for c in out["checks"]}
    assert out["e2e"]["train_tokens_per_s"] > 0 and out["steps"] >= 40
    assert out["first_sync"] % 40 == 20      # the window's place in the cycle
    assert checks["loss_gap"]["ok"] and checks["grad_norm_gap"]["ok"]
    assert checks["logged_loss_rise"]["ok"] is (not broken), checks
    assert out["correct"] is (not broken)


# ------------------------------------------------------ no chip, no result


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if c.endswith(("train-steady",
                                                 "serve-closed"))])
def test_without_a_chip_there_is_no_result_line(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout and '"metrics"' not in out.stdout
    assert "benchmark FAILED" in out.stderr


def test_unknown_workload_is_refused():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "no.such"],
        cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0 and out.stdout.strip() == ""


# ------------------------- a later PR adds files and entries, edits nothing


DUMMY_DRIVER = '''
def run(r):
    return {"correct": True, "attempted": 3, "failed": 0, "checks": [],
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
            "e2e": {"setup_s": 1.5, "dummy_rate": 7.0},
            "dummy_counter": r.workload["knob"] * r.config["n_embd"]}
'''
DUMMY_METRIC = '''
def read(run):
    return run.get("dummy_counter")
'''


def test_a_config_a_cell_and_a_metric_are_added_as_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    (root / "benchmark/configs/dummy-model.json").write_text(
        json.dumps({"source": "a paper", "n_embd": 8, "reduced": []}))
    (root / "benchmark/workloads/dummy-model.dummy-mix.json").write_text(
        json.dumps({"config": "dummy-model", "traffic": "dummy-mix",
                    "driver": "dummy_driver", "chips": 1, "knob": 3}))
    (root / "benchmark/drivers/dummy_driver.py").write_text(DUMMY_DRIVER)
    (root / "benchmark/layer_metrics/dummy.metric.py").write_text(DUMMY_METRIC)
    cell = "dummy-model.dummy-mix"
    bench["configs"].append({"name": "dummy-model", "source": "a paper",
                             "file": "benchmark/configs/dummy-model.json",
                             "reduced": [], "why": "shows the mechanism"})
    bench["workloads"].append({"name": cell, "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "shows the mechanism"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "dummy", "moves": "dummy_rate",
                               "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for trace, want in ((0, {"setup_s": 1.5, "dummy_rate": 7.0}),
                        (1, {"dummy.metric": 24})):
        out = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload", cell,
             "--seed", "1", "--seconds", "1", "--trace", str(trace),
             "--rehearse"], cwd=root,
            env=dict(os.environ, PYTHONPATH=str(root)),
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        line = json.loads(out.stdout.strip().splitlines()[-1])["would_print"]
        assert {k: v["value"] for k, v in line["metrics"].items()} == want
    # no file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())
