"""Program spans on the hot paths (ISSUE 25, DESIGN.md §32).

- ``hot_span`` pairs a journal span with a profiler annotation: with
  ``NullJournal`` and no capture it writes nothing; with a journal it
  nests, carries late fields on the end event and links to a remote
  parent; under a live capture the same name and fields land in the
  xplane's host plane.
- The trainer's block phase is a LAGGED wait: ~0 under a slow data
  iterator, ~a step under a slow step, one extra metrics reference at
  most, and the same losses and step count as waiting on every step.
- The snapshot writer's spans nest under the request that handed the
  copy over, across the thread boundary; a request that finds the
  writer busy records ``skipped``.
- The engine's ``decoding_slots`` (span field and gauge) is the active
  mask's count.
- ``dlrover_tpu_mfu`` divides the model's FLOPs, ``dlrover_tpu_hfu``
  the executable's; ``ckpt_restore`` closes after the state is ready.
"""

from __future__ import annotations

import gc
import os
import statistics
import sys
import threading
import time
import types
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.telemetry import journal as journal_mod
from dlrover_tpu.telemetry.journal import annotate, get_journal, hot_span
from dlrover_tpu.telemetry.report import load_events

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.delenv(EnvKey.JOURNAL_MAX_MB, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    yield str(tmp_path / "journal")
    journal_mod._cached = None


def events_of(journal_dir: str) -> list[dict]:
    return load_events(os.path.join(journal_dir, "events.jsonl"))


# ------------------------------------------------------------- the helper


def test_no_journal_and_no_capture_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv(EnvKey.JOURNAL_DIR, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    monkeypatch.chdir(tmp_path)
    assert not get_journal().enabled
    assert annotate("dispatch") is journal_mod._NO_ANNOTATION
    assert annotate("train_step", step_num=3) is journal_mod._NO_ANNOTATION
    with hot_span("engine_step", queued=2) as span:
        with hot_span("decode_block", slots=1) as child:
            child.set(n_steps=4)
        span.set(decoding_slots=1)
    assert span.id == "" and child.id == ""
    assert journal_mod.current_span_id() == ""
    assert os.listdir(tmp_path) == []
    journal_mod._cached = None


def test_hot_span_journals_nesting_late_fields_and_remote_parent(
        journal_dir):
    with hot_span("engine_step", queued=2) as outer:
        with hot_span("prefill_chunk", remote_parent="tr:gw1", tokens=7):
            pass
        outer.set(decoding_slots=3, n_steps=8)
    with hot_span("kv_install", remote_parent="tr:gw1", request=5):
        pass
    ev = events_of(journal_dir)
    begin = {e["name"]: e for e in ev if e["ev"] == "b"}
    end = {e["name"]: e for e in ev if e["ev"] == "e"}
    assert begin["engine_step"]["queued"] == 2
    assert "parent" not in begin["engine_step"]
    # a request's span belongs to the request's tree, whatever engine
    # step it ran in; without a request context it nests where it is
    assert begin["prefill_chunk"]["parent"] == "gw1"
    assert begin["kv_install"]["parent"] == "gw1"
    with hot_span("engine_step", queued=0) as again:
        with hot_span("prefill_chunk", remote_parent="", tokens=1):
            pass
    assert [e for e in events_of(journal_dir) if e["ev"] == "b"][-1][
        "parent"] == again.id
    assert end["engine_step"]["decoding_slots"] == 3
    assert end["engine_step"]["n_steps"] == 8
    assert end["engine_step"]["dur"] >= end["prefill_chunk"]["dur"] >= 0
    assert journal_mod.current_span_id() == ""


def test_hot_span_lands_in_the_profilers_host_plane(tmp_path, monkeypatch):
    """Under a live capture the xplane file holds the program's span with
    its fields, the late ones too, beside whatever the device planes
    hold: the shared clock. Read back with the benchmark's reducer."""
    monkeypatch.delenv(EnvKey.JOURNAL_DIR, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import span_reduce

    jax.profiler.start_trace(str(tmp_path))
    try:
        with annotate("train_step", step_num=41):
            with hot_span("engine_step", queued=3) as span:
                with annotate("dispatch"):
                    jnp.ones((8, 8)).sum().block_until_ready()
                span.set(decoding_slots=2)
    finally:
        jax.profiler.stop_trace()
    spans, _ = span_reduce.load_xplane(str(tmp_path))
    by_name = {s[0]: s for s in spans}
    assert set(by_name) == {"train_step", "engine_step", "dispatch"}
    assert by_name["train_step"][4]["step_num"] == 41
    assert by_name["engine_step"][4] == {"queued": 3, "decoding_slots": 2}
    lo, dur = by_name["engine_step"][1:3]
    assert lo <= by_name["dispatch"][1] \
        and by_name["dispatch"][1] + by_name["dispatch"][2] <= lo + dur
    journal_mod._cached = None


# ----------------------------------------------------- the lagged wait


class FakeDevice:
    """One queue, as a chip has: a step is dispatched at once and is done
    ``step_s`` after the later of its dispatch and the step before it."""

    def __init__(self, step_s: float):
        self.step_s = step_s
        self.free_at = 0.0
        self.live = weakref.WeakSet()

    def step(self, state, batch):
        done = max(self.free_at, time.monotonic()) + self.step_s
        self.free_at = done
        metrics = FakeMetrics(done)
        self.live.add(metrics)
        return types.SimpleNamespace(step=state.step + 1), metrics


class FakeMetrics:
    def __init__(self, done_at: float):
        self.done_at = done_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.done_at - time.monotonic()))
        return self


def fake_trainer(device: FakeDevice):
    from dlrover_tpu.parallel.strategy import dp
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    mesh = dp().build_mesh(jax.devices()[:1])
    compiled = types.SimpleNamespace(
        mesh=mesh, strategy=None, flops_per_step=0.0, step=device.step,
        batch_sharding=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    return ElasticTrainer(compiled, global_batch_size=1,
                          micro_batch_size=1, model_name="fake")


def batches(n: int, wait_s: float = 0.0):
    for _ in range(n):
        time.sleep(wait_s)
        yield {"x": np.zeros((1, 1), np.float32)}


@pytest.mark.parametrize("slow", ["data", "device"])
def test_block_is_zero_when_the_host_is_slow_and_a_step_when_the_device_is(
        journal_dir, slow):
    step_s, wait_s = (0.005, 0.04) if slow == "data" else (0.04, 0.0)
    device = FakeDevice(step_s)
    trainer = fake_trainer(device)
    t0 = time.monotonic()
    trainer.run_batches(types.SimpleNamespace(step=0),
                        batches(12, wait_s))
    took = time.monotonic() - t0
    points = [e for e in events_of(journal_dir)
              if e["name"] == "train_step"]
    assert [e["step"] for e in points] == list(range(2, 13))
    block = statistics.median(e["block_s"] for e in points[1:])
    data_wait = statistics.median(e["data_wait_s"] for e in points[1:])
    if slow == "data":
        assert block < 0.01 and data_wait > 0.03
        assert trainer.efficiency.host_blocked_frac() > 0.8
    else:
        # one step is in flight while the host waits for the one before:
        # the wait is a step long, and the loop runs at the device's pace
        assert 0.03 < block < 0.06 and data_wait < 0.01
        assert trainer.efficiency.host_blocked_frac() < 0.2
        assert took < 12 * step_s * 1.5
    # dur is the cadence: the end of the step before to this step's end
    cadence = statistics.median(e["dur"] for e in points[1:])
    assert cadence == pytest.approx(max(step_s, wait_s), rel=0.5)


def test_one_extra_metrics_reference_at_most_and_none_after_the_loop():
    device = FakeDevice(0.002)
    trainer = fake_trainer(device)
    state = types.SimpleNamespace(step=0)
    for batch in batches(5):
        state, metrics = trainer.train_step(state, batch)
        del metrics
        gc.collect()
        # the step just dispatched is held for the next step's wait
        assert len(device.live) == 1
    assert state.step == 5
    trainer.run_batches(state, batches(3))
    gc.collect()
    assert len(device.live) == 0


@pytest.mark.timeout(180)
def test_lagged_wait_leaves_losses_and_step_count_as_the_eager_wait():
    import optax

    from dlrover_tpu.models import transformer as T
    from dlrover_tpu.parallel import strategy as S
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
    from dlrover_tpu.trainer.train_step import compile_train

    cfg = T.CONFIGS["tiny"]
    strat = S.dp()
    mesh = strat.build_mesh(jax.devices()[:1])

    def fresh():
        compiled = compile_train(
            strategy=strat, mesh=mesh,
            loss_fn=T.make_loss_fn(cfg, strat, mesh),
            init_params_fn=lambda rng: T.init_params(cfg, rng),
            logical_params=T.logical_axes(cfg),
            optimizer=optax.adamw(1e-2),
        )
        return compiled, compiled.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    data = [{"tokens": rng.integers(0, cfg.vocab_size, (1, 2, 33),
                                    dtype=np.int32)} for _ in range(6)]
    compiled, state = fresh()
    eager = []
    for batch in data:        # the wait on every step, as it used to be
        state, metrics = compiled.step(
            state, jax.device_put(batch, compiled.batch_sharding))
        jax.block_until_ready(metrics)
        eager.append(float(metrics["loss"]))
    eager_step = int(state.step)

    compiled, state = fresh()
    trainer = ElasticTrainer(compiled, global_batch_size=2,
                             micro_batch_size=2, model_name="tiny")
    lagged = []
    state = trainer.run_batches(
        state, iter(data),
        on_step=lambda step, m: lagged.append(float(m["loss"])))
    assert int(state.step) == eager_step == 6
    assert lagged == eager


# ---------------------------------------------------------- the gauges


def test_mfu_divides_model_flops_and_hfu_the_executables():
    from dlrover_tpu.telemetry import efficiency as eff

    mon = eff.EfficiencyMonitor(model="m-hot", strategy="s",
                                flops_per_step=3e9, peak_flops=1e11,
                                num_devices=1, journal_every=0)
    mon.set_executable_flops(4e9)        # the recomputed forward counted
    mon.end_step(1, 0.1)
    assert mon.mfu() == pytest.approx(0.3)
    assert mon.hfu() == pytest.approx(0.4)
    assert eff.live_mfu("m-hot", "s") == pytest.approx(0.3)
    assert eff._hfu_gauge.labels("m-hot", "s").value == pytest.approx(0.4)
    # told no model FLOPs, the MFU gauge stays unset: never the
    # executable's count under the model's name
    bare = eff.EfficiencyMonitor(model="m-bare", strategy="s",
                                 peak_flops=1e11, journal_every=0)
    bare.set_executable_flops(4e9)
    bare.end_step(1, 0.1)
    assert bare.mfu() is None and bare.hfu() == pytest.approx(0.4)
    assert eff.live_mfu("m-bare", "s") is None


def test_model_flops_leave_out_recompute_and_count_the_causal_half():
    import dataclasses

    from dlrover_tpu.models import transformer as T

    cfg = dataclasses.replace(T.CONFIGS["tiny"], variant="gpt2")
    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    matmul = L * (4 * d * d + 2 * d * ff) + d * V
    seq = 64
    want = 3 * (2 * matmul + L * 4 * d * (seq + 1) / 2)
    assert cfg.train_flops_per_token(seq) == pytest.approx(want)
    both = dataclasses.replace(cfg, causal=False)
    assert both.train_flops_per_token(seq) > cfg.train_flops_per_token(seq)
    # the remat policy recomputes; the model's FLOPs do not move
    remat = dataclasses.replace(cfg, remat_scan=True)
    assert remat.train_flops_per_token(seq) == want


# --------------------------------------------------------- the snapshot


@pytest.fixture()
def engine(tmp_path, journal_dir):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    eng = CheckpointEngine(str(tmp_path / "ckpt"), node_id=31)
    yield eng
    eng.close()


def test_snapshot_spans_nest_under_the_request_across_the_thread(
        engine, journal_dir, monkeypatch):
    """The async path is gated off on the CPU backend (a second host
    thread touching arrays mid-collective wedges XLA:CPU): forced on
    here, on a single-device state with no collective."""
    monkeypatch.setattr(engine, "_async_ok", True)
    state = {"w": jnp.arange(1024, dtype=jnp.float32),
             "b": jnp.ones((8,), jnp.bfloat16)}
    release = threading.Event()
    inner = engine.shm_handler._fetch_packed

    def slow_fetch(named):
        release.wait(10)
        return inner(named)

    monkeypatch.setattr(engine.shm_handler, "_fetch_packed", slow_fetch)
    engine.save_to_memory_async(40, state)
    time.sleep(0.05)
    engine.save_to_memory_async(80, state)   # the writer is busy: skipped
    release.set()
    assert engine.flush_async(timeout=20)
    ev = events_of(journal_dir)
    requests = [e for e in ev if e["name"] == "snapshot_request"]
    begun = [e for e in requests if e["ev"] == "b"]
    ended = {e["span"]: e for e in requests if e["ev"] == "e"}
    assert [e["step"] for e in begun] == [40, 80]
    first, second = (ended[e["span"]] for e in begun)
    assert first["skipped"] is False and first["seq"] == 1
    assert second["skipped"] is True and "seq" not in second
    nbytes = 1024 * 4 + 8 * 2
    for name in ("snapshot_fetch", "snapshot_arena_write"):
        b, = [e for e in ev if e["name"] == name and e["ev"] == "b"]
        e, = [e for e in ev if e["name"] == name and e["ev"] == "e"]
        # the writer thread's spans are children of the request that
        # handed the copy over, on the main thread
        assert b["parent"] == begun[0]["span"]
        assert b["step"] == 40 and b["bytes"] >= nbytes
        assert e["dur"] >= 0
    # the request closed on the main thread while the writer still held
    # the copy: it does not wait for the fetch
    fetch_end, = [e for e in ev
                  if e["name"] == "snapshot_fetch" and e["ev"] == "e"]
    assert first["t"] < fetch_end["t"]
    loaded = engine.load({"w": np.zeros(1024, np.float32),
                          "b": np.zeros(8, jnp.bfloat16)})
    assert loaded[0] == 40
    np.testing.assert_array_equal(loaded[1]["w"], np.arange(1024))


def test_sync_snapshot_uses_the_same_names(engine, journal_dir):
    state = {"w": jnp.arange(16, dtype=jnp.float32)}
    engine.save_to_memory_async(7, state)   # CPU: falls back to blocking
    ev = events_of(journal_dir)
    begun = {e["name"]: e for e in ev if e["ev"] == "b"}
    assert set(begun) >= {"snapshot_request", "snapshot_fetch",
                          "snapshot_arena_write"}
    request = begun["snapshot_request"]["span"]
    assert begun["snapshot_fetch"]["parent"] == request
    assert begun["snapshot_arena_write"]["parent"] == request
    assert engine.save_to_storage(8, state)
    again = [e for e in events_of(journal_dir)
             if e["name"] == "snapshot_fetch" and e["ev"] == "b"]
    assert [e["step"] for e in again] == [7, 8]


def test_ckpt_restore_closes_after_the_state_is_ready(engine, journal_dir,
                                                      monkeypatch):
    state = {"w": jnp.arange(16, dtype=jnp.float32)}
    assert engine.save_to_memory(3, state)
    order = []
    real = jax.block_until_ready

    def ready(tree):
        order.append("ready")
        return real(tree)

    monkeypatch.setattr(jax, "block_until_ready", ready)

    def put(name, arr):
        order.append("put")
        return jnp.asarray(arr)

    step, restored = engine.load({"w": np.zeros(16, np.float32)}, put=put)
    restore, = [e for e in events_of(journal_dir)
                if e["name"] == "ckpt_restore"]
    assert step == 3 and order == ["put", "ready"]
    assert restore["step"] == 3 and restore["dur"] >= 0
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(16))


# ----------------------------------------------------------- the engine


def test_decoding_slots_is_the_active_masks_count(journal_dir):
    import dataclasses

    from dlrover_tpu.models import transformer as T
    from dlrover_tpu.serving import engine as E

    cfg = dataclasses.replace(T.CONFIGS["tiny"], variant="gpt2")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = E.InferenceEngine(params, cfg, slots=4, max_len=64,
                            prefill_len=8, decode_block=4)
    greedy = E.SamplingParams(temperature=0.0, max_new_tokens=6,
                              eos_id=None)
    gauge = E._decoding_slots.labels(eng.engine_id)
    # a script: one request decodes alone, two more join it, all finish
    eng.submit([5, 9, 2], greedy)
    seen = []

    def step():
        mask = None

        def spy(*args, **kw):
            nonlocal mask
            mask = np.asarray(args[8])  # (params, cache, last, seeds, ...)
            return real(*args, **kw)

        real = eng._step_block
        eng._step_block = spy
        try:
            eng.step()
        finally:
            eng._step_block = real
        seen.append((int(gauge.value),
                     None if mask is None else int(mask.sum())))

    step()
    eng.submit(list(range(1, 12)), greedy)     # two chunks of 8
    eng.submit([7, 7, 7, 7], greedy)
    while eng.outstanding:
        step()
    assert all(mask is None or slots == mask for slots, mask in seen)
    assert seen[0][0] == 1 and max(s for s, _ in seen) >= 2
    ev = events_of(journal_dir)
    ends = [e for e in ev if e["name"] == "engine_step" and e["ev"] == "e"]
    assert [e["decoding_slots"] for e in ends] == [s for s, _ in seen]
    blocks = [e for e in ev
              if e["name"] == "decode_block" and e["ev"] == "b"]
    assert [e["slots"] for e in blocks] == [s for s, _ in seen if s]
    steps = {e["span"] for e in ev
             if e["name"] == "engine_step" and e["ev"] == "b"}
    for name in ("prefill_chunk", "kv_install", "decode_block",
                 "engine_emit"):
        begun = [e for e in ev if e["name"] == name and e["ev"] == "b"]
        assert begun and all(e["parent"] in steps for e in begun), name
    chunks = [e for e in ev
              if e["name"] == "prefill_chunk" and e["ev"] == "b"]
    assert [(e["tokens"], e["chunk"], e["context"]) for e in chunks] == [
        (3, 0, 0), (8, 0, 0), (3, 1, 8), (4, 0, 0)]
