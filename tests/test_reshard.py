"""Elastic mesh resharding + the persistent compile cache (DESIGN.md §17).

Covers the three tentpole pieces in isolation and end to end:

- the master-side ``CompileCacheService`` (LRU bytes bound, coverage
  queries, fingerprint-mismatch-as-miss) and its RPC surface;
- the AOT executable round trip (``load_or_compile``: compile once,
  every later incarnation loads in ~0.1s and computes bit-identically)
  and the fallback-topology precompiler;
- ``reshard_state``: N -> N−1 -> N round-trips the train state
  bit-exactly (per-shard CRC via ``checkpoint/integrity.py``), through
  both the mesh-level remap and the engine's shm-snapshot path;
- the rendezvous shrink fast path (a node loss completes the round
  immediately as a ``reshard`` event, no waiting_timeout backoff);
- a chaos-harness kill scenario whose recovery trail shows ``reshard``
  + a cache-hit compile instead of a cold one.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint.integrity import crc32_bytes
from dlrover_tpu.master.kv_store import CompileCacheService, topology_tag
from dlrover_tpu.parallel import compile_cache as cc
from dlrover_tpu.parallel.mesh import build_mesh, remap_spec, reshard_state


# ----------------------------------------------------- master-side service


class TestCompileCacheService:
    def test_put_get_evict(self):
        svc = CompileCacheService()
        key = f"{topology_tag(8, 2)}/abc"
        assert svc.put(key, b"blob", {"m": 1})
        assert svc.get(key) == (b"blob", {"m": 1})
        assert svc.evict(key)
        assert svc.get(key) is None
        assert not svc.evict(key)

    def test_lru_byte_bound_evicts_oldest(self):
        svc = CompileCacheService(max_bytes=100)
        svc.put("t8n2/a", b"x" * 40)
        svc.put("t8n2/b", b"x" * 40)
        svc.get("t8n2/a")            # refresh a: b becomes LRU
        svc.put("t8n2/c", b"x" * 40)  # 120 bytes -> evict b
        assert svc.get("t8n2/b") is None
        assert svc.get("t8n2/a") is not None
        assert svc.get("t8n2/c") is not None
        assert svc.stats()["bytes"] <= 100

    def test_oversized_entry_refused(self):
        svc = CompileCacheService(max_bytes=100, max_entry_bytes=50)
        assert not svc.put("t8n2/big", b"x" * 51)
        assert svc.stats()["entries"] == 0

    def test_coverage_is_a_topology_prefix_scan(self):
        svc = CompileCacheService()
        svc.put(f"{topology_tag(8, 2)}/a", b"1")
        svc.put(f"{topology_tag(4, 1)}/b", b"2")
        assert svc.covers(topology_tag(8, 2)) == 1
        assert svc.covers(topology_tag(4, 1)) == 1
        assert svc.covers(topology_tag(16, 4)) == 0


class TestCompileCacheRpc:
    def test_put_get_query_round_trip(self):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.job_master import JobMaster

        master = JobMaster(port=0, rdzv_timeout=2.0)
        master.prepare()
        try:
            c = MasterClient(master.addr, 0)
            tag = topology_tag(8, 2)
            blob = bytes(range(256)) * 64  # binary payload over serde
            assert c.compile_cache_put(f"{tag}/k1", blob,
                                       {"inputs": {"model": "tiny"}})
            got = c.compile_cache_get(f"{tag}/k1")
            assert got is not None
            assert got[0] == blob
            assert got[1]["inputs"]["model"] == "tiny"
            assert c.compile_cache_get(f"{tag}/other") is None
            q = c.compile_cache_query(tag)
            assert q.covered and q.executables == 1
            assert not c.compile_cache_query(topology_tag(4, 1)).covered
            c.close()
        finally:
            master.stop()


# -------------------------------------------------- fingerprint + envelope


class TestFingerprint:
    def _fp(self, **over):
        kw = dict(num_nodes=2, total_devices=8,
                  mesh_axes={"data": 8}, model={"layers": 2},
                  strategy={"name": "dp"}, args_signature=[[8, 4]],
                  extra={})
        kw.update(over)
        return cc.compile_fingerprint(**kw)

    def test_stable_and_topology_prefixed(self):
        key1, inputs = self._fp()
        key2, _ = self._fp()
        assert key1 == key2
        assert key1.startswith(topology_tag(8, 2) + "/")
        assert inputs["jax"] == jax.__version__

    def test_the_packages_code_is_an_input(self, monkeypatch):
        """What a program computes is not in the config or the calling
        convention: an executable cached by other code must not match."""
        base, inputs = self._fp()
        assert inputs["code"] == cc.code_digest() and len(inputs["code"]) == 32
        monkeypatch.setattr(cc, "code_digest", lambda: "0" * 32)
        other, other_inputs = self._fp()
        assert other != base and other_inputs != inputs

    def test_every_input_changes_the_key(self):
        base, _ = self._fp()
        assert self._fp(model={"layers": 3})[0] != base
        assert self._fp(strategy={"name": "fsdp"})[0] != base
        assert self._fp(num_nodes=1)[0] != base
        assert self._fp(mesh_axes={"data": 4, "tensor": 2})[0] != base
        assert self._fp(args_signature=[[16, 4]])[0] != base


def _tiny_aot():
    """A small sharded+donated executable (compiles in well under 1s)."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def step(w, x):
        y = jnp.tanh(x @ w)
        return w - 0.01 * y.sum() * w, (y * y).mean()

    jitted = jax.jit(step, in_shardings=(rep, sh),
                     out_shardings=(rep, rep), donate_argnums=(0,))

    def fresh_args():
        # donation consumes w on every call: hand out fresh buffers
        return (jax.device_put(jnp.arange(64.0).reshape(8, 8) / 64.0,
                               rep),
                jax.device_put(jnp.ones((8, 8)), sh))

    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        fresh_args())
    return jitted, abstract, fresh_args


class TestEnvelope:
    def test_round_trip_is_bit_identical(self):
        jitted, abstract, fresh_args = _tiny_aot()
        compiled = jitted.lower(*abstract).compile()
        _, ref = compiled(*fresh_args())
        blob = cc.serialize_executable_blob(compiled, {"k": 1})
        loaded = cc.load_executable_blob(blob, expect_inputs={"k": 1})
        assert loaded is not None
        _, got = loaded(*fresh_args())
        assert float(got) == float(ref)

    def test_corruption_and_mismatch_read_as_miss(self):
        jitted, abstract, _ = _tiny_aot()
        compiled = jitted.lower(*abstract).compile()
        blob = cc.serialize_executable_blob(compiled, {"k": 1})
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 0x10
        assert cc.load_executable_blob(bytes(flipped)) is None
        # same digest, different recorded inputs -> fingerprint
        # mismatch -> miss (never a wrong program)
        assert cc.load_executable_blob(blob,
                                       expect_inputs={"k": 2}) is None
        assert cc.load_executable_blob(b"junk") is None


    @pytest.mark.parametrize("device_ids", [[0], [5], [0, 1, 2, 3]])
    def test_loads_onto_the_devices_it_was_compiled_for(self, device_ids):
        """A program for fewer devices than the process sees (a
        one-chip serving replica on a four-chip host; here the 8-device
        CPU mesh) must load onto ITS devices: left to its default,
        deserialize_and_load takes all 8 and the first call fails with
        "Expected args ... to have 8 shards"."""
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array([jax.devices()[i] for i in device_ids]),
                    ("d",))
        sharding = NamedSharding(mesh, PartitionSpec("d"))
        x = jax.device_put(np.arange(8 * 4, dtype=np.float32).reshape(8, 4),
                           sharding)
        compiled = jax.jit(lambda a: (a * 2 + 1, a.sum())).lower(
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        ).compile()
        client = cc.CompileCacheClient()  # the per-run dir of conftest
        key = f"t/one_device_{'_'.join(map(str, device_ids))}"
        client.put(key, cc.serialize_executable_blob(compiled, {"k": 1}))
        blob, layer = client.get(key)
        assert layer == "local"
        loaded = cc.load_executable_blob(blob, expect_inputs={"k": 1})
        assert loaded is not None
        doubled, total = loaded(x)
        np.testing.assert_array_equal(np.asarray(doubled),
                                      np.asarray(x) * 2 + 1)
        assert float(total) == float(np.asarray(x).sum())
        assert {d.id for d in doubled.sharding.device_set} \
            == set(device_ids)


class TestLoadOrCompile:
    def test_miss_compiles_then_hit_loads(self, tmp_path):
        jitted, abstract, fresh_args = _tiny_aot()
        client = cc.CompileCacheClient(local_dir=str(tmp_path / "aot"))
        key, inputs = cc.compile_fingerprint(
            num_nodes=1, total_devices=8, mesh_axes={"data": 8},
            model={"t": "tiny_aot"}, strategy={"name": "dp"},
            args_signature=cc.abstract_signature(abstract),
        )
        first = cc.load_or_compile(
            key, inputs,
            compile_fn=lambda: jitted.lower(*abstract).compile(),
            cache=client)
        assert not first.cache_hit and first.source == "compiled"
        _, ref = first.fn(*fresh_args())
        second = cc.load_or_compile(
            key, inputs,
            compile_fn=lambda: pytest.fail("hit must not compile"),
            cache=client)
        assert second.cache_hit and second.source == "local"
        _, got = second.fn(*fresh_args())
        assert float(got) == float(ref)

    def test_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_AOT_CACHE", "0")
        jitted, abstract, _ = _tiny_aot()
        client = cc.CompileCacheClient(local_dir=str(tmp_path / "aot"))
        got = cc.load_or_compile(
            "t8n1/x", {},
            compile_fn=lambda: jitted.lower(*abstract).compile(),
            cache=client)
        assert not got.cache_hit and got.source == "disabled"
        assert not os.path.exists(str(tmp_path / "aot"))

    def test_local_prune_keeps_newest(self, tmp_path):
        client = cc.CompileCacheClient(local_dir=str(tmp_path / "aot"),
                                       max_local_files=10)
        base = time.time() - 100
        for i in range(4):
            client.put(f"t8n1/k{i}", b"blob%d" % i)
            # strictly ordered mtimes in the PAST (a future mtime would
            # make the freshly written file look oldest)
            os.utime(client._path(f"t8n1/k{i}"), (base + i, base + i))
        client.max_local_files = 2
        client._prune()
        files = sorted(os.listdir(str(tmp_path / "aot")))
        assert files == ["t8n1_k2.aot", "t8n1_k3.aot"]


class TestFallbackPrecompiler:
    def test_precompiles_and_publishes_smaller_world(self, tmp_path):
        client = cc.CompileCacheClient(local_dir=str(tmp_path / "aot"))
        built_for: list[int] = []

        def build_fn(n_nodes: int):
            if n_nodes != 1:
                return None  # only the 4-device single-node fallback
            built_for.append(n_nodes)
            devices = jax.devices()[:4]
            mesh = Mesh(np.array(devices).reshape(4), ("data",))
            sh = NamedSharding(mesh, P("data"))
            rep = NamedSharding(mesh, P())
            jitted = jax.jit(lambda w, x: (x @ w).sum(),
                             in_shardings=(rep, sh), out_shardings=rep)
            abstract = (jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                             sharding=rep),
                        jax.ShapeDtypeStruct((4, 8), jnp.float32,
                                             sharding=sh))
            key, inputs = cc.compile_fingerprint(
                num_nodes=n_nodes, total_devices=4,
                mesh_axes={"data": 4}, model={"t": "fb"},
                strategy={"name": "dp"},
                args_signature=cc.abstract_signature(abstract),
            )
            return key, inputs, (
                lambda: jitted.lower(*abstract).compile())

        pre = cc.FallbackPrecompiler(
            build_fn, world_sizes=[1, 3], cache=client, delay_s=0.0,
        ).start()
        assert pre.wait(timeout=120)
        assert pre.results[1] == "published"
        assert pre.results[3] == "infeasible"
        assert built_for == [1]
        # the published artifact is loadable and keyed by the topology
        key = [k for k in os.listdir(str(tmp_path / "aot"))]
        assert len(key) == 1 and key[0].startswith("n1t4_")
        # re-arming skips work: already cached
        again = cc.FallbackPrecompiler(
            build_fn, world_sizes=[1], cache=client, delay_s=0.0,
        ).start()
        assert again.wait(timeout=30)
        assert again.results[1] == "already_cached"


# --------------------------------------------------------- state reshard


def _sharded_state(mesh):
    """A TrainState-shaped pytree with mixed layouts: replicated step,
    data-sharded 'dp' leaf, tensor-ish 2D shard, odd-shaped leaf."""
    put = lambda arr, spec: jax.device_put(  # noqa: E731
        arr, NamedSharding(mesh, spec))
    axes = list(mesh.axis_names)
    first = axes[0]
    return {
        "step": put(jnp.asarray(7, jnp.int32), P()),
        "w_dp": put(jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
                    P(first)),
        "w_2d": put(jnp.arange(64, dtype=jnp.bfloat16).reshape(8, 8),
                    P(None, first)),
        "bias": put(jnp.arange(24, dtype=jnp.float32), P()),
    }


def _shard_crcs(state) -> dict[str, int]:
    """Per-LEAF CRC of the fully-gathered bytes: layout-independent
    identity (per-device shard boundaries legitimately move across a
    reshard; the bytes must not)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        host = np.asarray(jax.device_get(leaf))
        out[str(path)] = crc32_bytes(host.tobytes())
    return out


class TestReshardState:
    def test_n_to_n_minus_1_to_n_is_bit_exact(self):
        mesh8 = build_mesh({"data": -1}, devices=jax.devices())
        mesh4 = build_mesh({"data": -1}, devices=jax.devices()[:4])
        state = _sharded_state(mesh8)
        before = _shard_crcs(state)

        shrunk = reshard_state(mesh8, mesh4, state)
        # every leaf actually lives on the 4-device mesh, same specs
        for leaf in jax.tree_util.tree_leaves(shrunk):
            assert leaf.sharding.mesh.devices.size == 4
        assert shrunk["w_dp"].sharding.spec == P("data")
        assert _shard_crcs(shrunk) == before

        back = reshard_state(mesh4, mesh8, shrunk)
        for leaf in jax.tree_util.tree_leaves(back):
            assert leaf.sharding.mesh.devices.size == 8
        assert _shard_crcs(back) == before
        # per-device shards on the restored mesh match the original
        # layout exactly too
        for name in ("w_dp", "w_2d"):
            orig = [crc32_bytes(np.asarray(s.data).tobytes())
                    for s in state[name].addressable_shards]
            rest = [crc32_bytes(np.asarray(s.data).tobytes())
                    for s in back[name].addressable_shards]
            assert orig == rest

    def test_dropped_axis_replicates(self):
        mesh = build_mesh({"data": 4, "tensor": 2},
                          devices=jax.devices())
        mesh_dp = build_mesh({"data": -1}, devices=jax.devices()[:4])
        assert remap_spec(P("tensor"), mesh_dp) == P()
        assert remap_spec(P(None, ("data", "tensor")), mesh_dp) \
            == P(None, "data")
        state = {
            "w": jax.device_put(
                jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                NamedSharding(mesh, P("data", "tensor"))),
        }
        before = _shard_crcs(state)
        moved = reshard_state(mesh, mesh_dp, state)
        assert moved["w"].sharding.spec == P("data")
        assert _shard_crcs(moved) == before

    def test_reshard_emits_metric_and_journal(self, tmp_path,
                                              monkeypatch):
        # get_journal() re-resolves when the dir env changes: no reset
        monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR", str(tmp_path))
        mesh8 = build_mesh({"data": -1}, devices=jax.devices())
        mesh4 = build_mesh({"data": -1}, devices=jax.devices()[:4])
        reshard_state(mesh8, mesh4, _sharded_state(mesh8))
        events = [json.loads(line) for line in
                  open(tmp_path / "events.jsonl")]
        reshards = [e for e in events if e["name"] == "reshard"]
        assert reshards and reshards[0]["leaves"] == 4
        assert reshards[0]["new_devices"] == 4

    def test_engine_reshard_uses_the_shm_snapshot(self, tmp_ipc_dir,
                                                  tmp_path):
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        mesh8 = build_mesh({"data": -1}, devices=jax.devices())
        mesh4 = build_mesh({"data": -1}, devices=jax.devices()[:4])
        state = _sharded_state(mesh8)
        before = _shard_crcs(state)
        eng = CheckpointEngine(str(tmp_path / "ckpt"))
        try:
            shrunk = eng.reshard_state(mesh8, mesh4, state, step=7)
            assert _shard_crcs(shrunk) == before
            for leaf in jax.tree_util.tree_leaves(shrunk):
                assert leaf.sharding.mesh.devices.size == 4
            # the reshard's snapshot doubles as the rollback point
            loaded = eng.load_raw()
            assert loaded is not None and loaded[0] == 7
        finally:
            eng.close()


# ------------------------------------------- rendezvous shrink fast path


class TestRendezvousShrinkFastPath:
    def test_node_loss_completes_immediately_as_reshard(self):
        from dlrover_tpu.master.rdzv_manager import RendezvousManager

        mgr = RendezvousManager(min_nodes=1, max_nodes=3,
                                waiting_timeout=30.0)
        for nid in (0, 1, 2):
            mgr.join(nid, f"n{nid}:1", 4)
        first = mgr.get_comm_world(0)
        assert first is not None and not first.reshard
        # node 2 dies; survivors re-join — the round must complete NOW
        # (no 30s backoff) and be marked a reshard event
        mgr.remove_node(2)
        mgr.join(0, "n0:2", 4)
        assert mgr.get_comm_world(0) is None  # partial: node 1 missing
        mgr.join(1, "n1:2", 4)
        t0 = time.monotonic()
        world = mgr.get_comm_world(0)
        assert time.monotonic() - t0 < 0.1
        assert world is not None and world.reshard
        assert set(world.world) == {0, 1}
        assert world.total_devices == 8

    def test_departed_member_rejoining_disables_both_fast_paths(self):
        from dlrover_tpu.master.rdzv_manager import RendezvousManager

        mgr = RendezvousManager(min_nodes=2, max_nodes=3,
                                waiting_timeout=0.5)
        for nid in (0, 1):
            mgr.join(nid, f"n{nid}:1", 4)
        time.sleep(0.6)
        assert mgr.get_comm_world(0) is not None
        mgr.remove_node(1)
        mgr.join(0, "n0:2", 4)
        mgr.join(1, "n1:2", 4)  # the "dead" node came back: full round
        assert mgr.get_comm_world(0) is None
        time.sleep(0.6)
        world = mgr.get_comm_world(0)
        assert world is not None and not world.reshard
        assert set(world.world) == {0, 1}

    def test_shrink_below_min_nodes_waits(self):
        from dlrover_tpu.master.rdzv_manager import RendezvousManager

        mgr = RendezvousManager(min_nodes=2, max_nodes=2,
                                waiting_timeout=0.3)
        for nid in (0, 1):
            mgr.join(nid, f"n{nid}:1", 4)
        time.sleep(0.4)
        assert mgr.get_comm_world(0) is not None
        mgr.remove_node(1)
        mgr.join(0, "n0:2", 4)
        time.sleep(0.4)
        assert mgr.get_comm_world(0) is None  # 1 < min_nodes: no world


# ------------------------------------------------- chaos: reshard trail


@pytest.mark.timeout(300)
def test_kill_recovery_trail_shows_reshard_and_warm_compile(tmp_path):
    """The tentpole end to end, under the chaos harness: the trainer is
    SIGKILLed mid-run; incarnation 0 published its executable, so the
    master's coverage query makes the recovery a *reshard* event (trail
    shows ``reshard``) and the promoted standby's "recompile" is a
    cache-hit load, not a cold XLA compile."""
    from dlrover_tpu.chaos.scenario import (
        JobLeg,
        Scenario,
        _read_journal,
        run_scenario,
    )

    scenario = Scenario(
        name="kill_reshard", seed=777,
        legs=[JobLeg(
            name="kill_warm", max_steps=12,
            faults=[{"point": "agent_kill_trainer", "action": "kill",
                     "args": {"sig": 9},
                     "match": {"step_gte": 6}, "times": 1}],
            train_args=["--ckpt-interval", "1000000",
                        "--mem-ckpt-interval", "2",
                        "--step-delay", "0.12"],
        )],
    )
    work = str(tmp_path / "run")
    res = run_scenario(
        scenario, work,
        env_extra={"JAX_PLATFORMS": "cpu",
                   "DLROVER_TPU_DEVICE_COUNT": "1",
                   "DLROVER_TPU_STANDBY": "1"},
        deadline_s=160,
    )
    res.assert_invariants()
    assert res.legs[0].result["restart_count"] == 1
    assert res.legs[0].result["final_step"] == 12

    # the recovery trail records the reshard choice (1 node, no shrink)
    assert ["reshard", 1, False] in res.trail["recovery"]

    events = _read_journal(os.path.join(work, "journal"))
    compiles = [e for e in events if e.get("name") == "compile"]
    assert len(compiles) == 2, compiles
    # incarnation 0 compiled cold; the promoted standby loaded the
    # cached executable — recovery skipped the recompile cost class
    assert compiles[0].get("cache_hit") is False
    assert compiles[1].get("cache_hit") is True
    cache_events = [e for e in events
                    if e.get("name") == "compile_cache"]
    assert len(cache_events) == 2, cache_events
    assert cache_events[0]["hit"] is False  # inc 0: compile + publish
    assert cache_events[1]["hit"] is True   # promoted standby: load
    # the warm "recompile" is an executable load: ≥5x under the cold
    # XLA compile (the acceptance floor; local loads measure ~20-30x)
    assert cache_events[1]["dur"] <= cache_events[0]["dur"] / 5.0

    # and the lost-time report splits the categories accordingly
    from dlrover_tpu.telemetry.report import build_report

    rep = build_report(os.path.join(work, "journal"))
    assert rep.categories["recompile_cold"] > 0
    assert rep.categories["recompile_warm"] >= 0
    assert rep.categories["recompile_warm"] \
        <= rep.categories["recompile_cold"] / 5.0
