"""Per-host parallel persist + topology-changing verified restore
(DESIGN.md §20).

Covers the PR-9 tentpole end to end: the object-store storage contract,
replica-group dedup on the write path, quorum restore semantics
(partial-manifest and missing-writer steps skipped, per-shard rollback
to the replica twin), N→M→N restore bit-exactness for M<N and M>N, the
persist-ack RPC, the typed persist/restore timeout results, the canned
sharded chaos scenario's replay-identical trail, and the gateway
replica AOT cold-start wiring.

Multi-host saves are simulated with several solo-mode engines sharing a
checkpoint dir (the CPU backend cannot run multiprocess collectives in
this container; everything under test — storage, commit, verify,
reassembly — is process-count-agnostic).
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint import integrity
from dlrover_tpu.checkpoint.engine import (
    CheckpointEngine,
    PersistWait,
    RestorePrefetch,
    _storage_fallback_leaf,
)
from dlrover_tpu.checkpoint.sharded import (
    ShardedCheckpointEngine,
    assemble,
    storage_piece_registry,
)
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage


# ------------------------------------------------------- storage contract


class TestStorageContract:
    """Semantics any CheckpointStorage backend must satisfy; run any new
    backend class through this by overriding ``storage``/``root``."""

    @pytest.fixture()
    def storage(self):
        return PosixDiskStorage()

    def test_write_parallel_matches_write(self, storage, tmp_path):
        blob = np.random.default_rng(0).bytes(3 << 20)
        a = str(tmp_path / "a.bin")
        b = str(tmp_path / "b.bin")
        storage.write(blob, a)
        storage.write_parallel(blob, b, chunk_bytes=1 << 20, workers=3)
        assert storage.read(a) == storage.read(b) == blob
        assert storage.size(b) == len(blob)

    def test_write_parallel_is_atomic(self, storage, tmp_path):
        path = str(tmp_path / "x.bin")
        storage.write_parallel(b"v1" * 100, path)
        storage.write_parallel(b"v2" * 100, path, chunk_bytes=1 << 20)
        assert storage.read(path) == b"v2" * 100
        # no tmp debris left behind
        assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []

    def test_read_range_semantics(self, storage, tmp_path):
        path = str(tmp_path / "r.bin")
        blob = bytes(range(256)) * 16
        storage.write(blob, path)
        assert storage.read_range(path, 0, 10) == blob[:10]
        assert storage.read_range(path, 100, 50) == blob[100:150]
        # short only at end-of-object (ranged-GET semantics)
        assert storage.read_range(path, len(blob) - 4, 100) == blob[-4:]

    def test_default_impls_fall_back_to_whole_blob(self, tmp_path):
        class MinimalStorage(CheckpointStorage):
            def __init__(self):
                self.blobs: dict[str, bytes] = {}

            def write(self, content, path):
                self.blobs[path] = (
                    content if isinstance(content, bytes)
                    else content.encode()
                )

            def read(self, path):
                return self.blobs[path]

            def exists(self, path):
                return path in self.blobs

            def listdir(self, path):
                return sorted(
                    p[len(path) + 1:] for p in self.blobs
                    if p.startswith(path + "/")
                )

            def makedirs(self, path):
                pass

            def delete(self, path):
                self.blobs.pop(path, None)

        s = MinimalStorage()
        s.write_parallel(b"hello world", "k")
        assert s.read("k") == b"hello world"
        assert s.read_range("k", 6, 5) == b"world"
        assert s.size("k") == 11


# ------------------------------------------------- multi-host save helper


def _host_pieces(data: np.ndarray, i: int, hosts: int,
                 twins: bool) -> tuple[dict, dict]:
    """Host ``i`` owns rows [i*k,(i+1)*k) as replica 0; with ``twins``
    it also carries host i-1's rows as the replica-1 ring twin."""
    rows, cols = data.shape
    k = rows // hosts
    holders = [(0, i)] + ([(1, (i - 1) % hosts)] if twins else [])
    pieces, index = {}, {}
    for replica, owner in holders:
        key = f"w::piece{replica}"
        pieces[key] = data[owner * k:(owner + 1) * k]
        index[key] = {
            "path": "w", "global_shape": [rows, cols],
            "dtype": "float32",
            "index": [[owner * k, (owner + 1) * k], [0, cols]],
            "replica": replica, "persist": True,
        }
    return pieces, index


def _save_hosts(ckpt_dir: str, legs, hosts: int, twins: bool = False):
    """N solo engines persist ``legs`` = [(step, data, skip), ...] in
    order; rank 0 joins each commit. Hosts in a leg's ``skip`` snapshot
    but never persist (died mid-save). One engine set serves every leg
    — engine construction (shm + IPC servers) dominates test wall time
    otherwise."""
    engines = [
        ShardedCheckpointEngine(ckpt_dir, node_id=i, node_rank=i,
                                world_size=hosts)
        for i in range(hosts)
    ]
    try:
        for step, data, skip in legs:
            for i, eng in enumerate(engines):
                pieces, index = _host_pieces(data, i, hosts, twins)
                eng.snapshot_pieces(step, pieces, index)
                if i != 0 and i not in skip:
                    eng._solo_saver._persist_step(step)
            if 0 not in skip:
                engines[0]._solo_saver._persist_step(
                    step, commit_block_s=0.0 if skip else 30.0
                )
    finally:
        for eng in engines:
            eng.shm_handler.close(unlink=True)
            eng.close()


STORAGE = PosixDiskStorage()


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _restore_rows(ckpt_dir: str, rows: int, cols: int,
                  m_hosts: int) -> tuple[int, np.ndarray, list[str]]:
    plan = integrity.resolve_restore_plan(STORAGE, ckpt_dir)
    assert plan is not None
    registry = storage_piece_registry(
        STORAGE, ckpt_dir, plan.step, plan.num_shards,
        bad_pieces=plan.bad_pieces,
    )
    bounds = [round(rows * j / m_hosts) for j in range(m_hosts + 1)]
    parts = [
        assemble([[bounds[j], bounds[j + 1]], [0, cols]],
                 np.dtype("float32"), registry["w"])
        for j in range(m_hosts)
    ]
    return plan.step, np.concatenate(parts), sorted(plan.bad_pieces)


class TestQuorumRestore:
    ROWS, COLS, HOSTS = 24, 8, 3

    def _data(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(1000 + step)
        return rng.standard_normal((self.ROWS, self.COLS)).astype(
            np.float32)

    def test_replica_dedup_writes_each_shard_once(self, tmp_ipc_dir,
                                                  tmp_path):
        """replicas=1: every global piece index appears exactly once
        across all node files — no write amplification."""
        ckpt = str(tmp_path / "ckpt")
        _save_hosts(ckpt, [(3, self._data(3), set())], self.HOSTS)
        seen = []
        sdir = os.path.join(ckpt, "step-3")
        for i in range(self.HOSTS):
            meta = json.loads(
                open(os.path.join(sdir, f"node_{i}.meta.json")).read())
            for entry in meta["sharded_index"].values():
                seen.append(tuple(map(tuple, entry["index"])))
        assert len(seen) == len(set(seen)) == self.HOSTS

    def test_nonpersist_pieces_stay_out_of_storage(self, tmp_ipc_dir,
                                                   tmp_path,
                                                   monkeypatch):
        """Twin pieces exist in shm (full local coverage) but are
        stripped from the persisted bin when replicas=1."""
        ckpt = str(tmp_path / "ckpt")
        monkeypatch.setenv("DLROVER_TPU_CKPT_PERSIST_REPLICAS", "1")
        data = self._data(5)
        eng = ShardedCheckpointEngine(ckpt, node_id=0, node_rank=0,
                                      world_size=1)
        try:
            pieces, index = _host_pieces(data, 0, self.HOSTS, twins=True)
            # the ring twin is replica 1 -> persist=False at replicas=1
            index["w::piece1"]["persist"] = False
            eng.snapshot_pieces(5, pieces, index)
            eng._solo_saver._persist_step(5, commit_block_s=30.0)
            meta = json.loads(open(os.path.join(
                ckpt, "step-5", "node_0.meta.json")).read())
            assert list(meta["sharded_index"]) == ["w::piece0"]
            k = self.ROWS // self.HOSTS
            assert os.path.getsize(os.path.join(
                ckpt, "step-5", "node_0.bin")) == k * self.COLS * 4
            # shm snapshot still holds BOTH pieces (restart-in-place)
            raw = eng.shm_handler.header()
            assert set(raw["sharded_index"]) == {"w::piece0",
                                                 "w::piece1"}
        finally:
            eng.shm_handler.close(unlink=True)
            eng.close()

    def test_missing_writer_step_skipped(self, tmp_ipc_dir, tmp_path):
        """A host dead mid-save leaves no marker/ack: the step never
        commits and restore serves the previous one."""
        ckpt = str(tmp_path / "ckpt")
        _save_hosts(ckpt, [(3, self._data(3), set()),
                           (7, self._data(7), {2})], self.HOSTS)
        step, got, bad = _restore_rows(ckpt, self.ROWS, self.COLS, 2)
        assert step == 3 and bad == []
        assert _crc(got) == _crc(self._data(3))

    def test_partial_manifest_step_skipped(self, tmp_ipc_dir, tmp_path):
        """A commit manifest listing fewer writers than the world is
        incomplete — the quorum walk rejects it."""
        ckpt = str(tmp_path / "ckpt")
        _save_hosts(ckpt, [(3, self._data(3), set()),
                           (7, self._data(7), set())], self.HOSTS)
        sdir = os.path.join(ckpt, "step-7")
        marker = os.path.join(sdir, integrity.commit_marker(self.HOSTS))
        manifest = json.loads(open(marker).read())
        del manifest["shards"]["1"]
        with open(marker, "w") as f:
            json.dump(manifest, f)
        verdict = integrity.verify_step_quorum(STORAGE, sdir, self.HOSTS)
        assert verdict.fail_kind == "incomplete_manifest"
        step, got, _ = _restore_rows(ckpt, self.ROWS, self.COLS, 2)
        assert step == 3
        assert _crc(got) == _crc(self._data(3))

    def test_corrupt_shard_without_twin_rolls_whole_step(
            self, tmp_ipc_dir, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        _save_hosts(ckpt, [(3, self._data(3), set()),
                           (7, self._data(7), set())], self.HOSTS)
        path = os.path.join(ckpt, "step-7", "node_1.bin")
        blob = bytearray(open(path, "rb").read())
        blob[17] ^= 0x20
        with open(path, "wb") as f:
            f.write(bytes(blob))
        step, got, _ = _restore_rows(ckpt, self.ROWS, self.COLS, 2)
        assert step == 3
        assert _crc(got) == _crc(self._data(3))

    def test_per_shard_rollback_picks_replica_twin(self, tmp_ipc_dir,
                                                   tmp_path,
                                                   monkeypatch):
        """replicas=2: the corrupt primary's pieces restore from the
        ring twin — the step survives, newest data bit-exact."""
        monkeypatch.setenv("DLROVER_TPU_CKPT_PERSIST_REPLICAS", "2")
        monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR",
                           str(tmp_path / "journal"))
        ckpt = str(tmp_path / "ckpt")
        _save_hosts(ckpt, [(3, self._data(3), set()),
                           (7, self._data(7), set())], self.HOSTS,
                    twins=True)
        path = os.path.join(ckpt, "step-7", "node_1.bin")
        blob = bytearray(open(path, "rb").read())
        blob[5] ^= 0x01
        with open(path, "wb") as f:
            f.write(bytes(blob))
        step, got, bad = _restore_rows(ckpt, self.ROWS, self.COLS, 2)
        assert step == 7 and "1" in bad
        assert _crc(got) == _crc(self._data(7))
        events = [
            json.loads(line) for line in
            open(tmp_path / "journal" / "events.jsonl")
        ]
        rb = [e for e in events if e["name"] == "ckpt_shard_rollback"]
        assert rb and rb[0]["writer"] == "1" and rb[0]["step"] == 7

    def test_reshard_storage_fallback_leaf(self, tmp_ipc_dir, tmp_path):
        """The reshard path's missing-shard net: a leaf with no live
        copy assembles in full from the committed step."""
        ckpt = str(tmp_path / "ckpt")
        data = self._data(4)
        _save_hosts(ckpt, [(4, data, set())], self.HOSTS)
        box: list = []
        leaf = jax.ShapeDtypeStruct((self.ROWS, self.COLS), np.float32)
        got = _storage_fallback_leaf(STORAGE, ckpt, "w", leaf, box)
        assert got is not None
        np.testing.assert_array_equal(got, data)
        assert _storage_fallback_leaf(
            STORAGE, ckpt, "nope", leaf, box) is None


# ---------------------------------------------- topology-changing (jax)


def _owned_by(node: int, split: int):
    def owned(shard):
        return (shard.replica_id == 0
                and (shard.device.id < split) == (node == 0))
    return owned


class TestTopologyChangingRestore:
    """Save on N writers, restore onto smaller AND larger meshes,
    round-trip back — bit-exact at every hop."""

    def _mesh(self, n):
        from dlrover_tpu.parallel.mesh import build_mesh

        return build_mesh({"data": -1}, devices=jax.devices()[:n])

    def _state(self, mesh):
        s = {
            "w": jnp.arange(64, dtype=jnp.float32).reshape(16, 4),
            "b": jnp.arange(16, dtype=jnp.float32) * 0.5,
            "step": jnp.asarray(9, jnp.int32),
        }
        specs = {"w": P("data"), "b": P("data"), "step": P()}
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in s.items()
        }, specs

    def test_n_to_m_to_n_bit_exact(self, tmp_ipc_dir, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        mesh8 = self._mesh(8)
        state, specs = self._state(mesh8)
        crcs = {k: _crc(np.asarray(jax.device_get(v)))
                for k, v in state.items()}
        e0 = ShardedCheckpointEngine(ckpt, node_id=0, node_rank=0,
                                     world_size=2,
                                     owned=_owned_by(0, 4))
        e1 = ShardedCheckpointEngine(ckpt, node_id=1, node_rank=1,
                                     world_size=2,
                                     owned=_owned_by(1, 4))
        try:
            assert e1.save_to_storage(9, state)
            assert e0.save_to_storage(9, state)
            assert e0.wait_for_persist(9, timeout=60)
        finally:
            for e in (e0, e1):
                e.shm_handler.close(unlink=True)
                e.close()

        # M < N: restore the 2-writer checkpoint onto 4 devices
        mesh4 = self._mesh(4)
        sh4 = {k: NamedSharding(mesh4, specs[k]) for k in state}
        em = ShardedCheckpointEngine(str(tmp_path / "ckpt"), node_id=5,
                                     world_size=1)
        try:
            loaded = em.load_sharded(state, sh4)
            assert loaded is not None and loaded[0] == 9
            small = loaded[1]
            for k in state:
                assert _crc(np.asarray(jax.device_get(small[k]))) \
                    == crcs[k], k
            # save from the shrunk world, then M > N: back onto 8
            ckpt2 = str(tmp_path / "ckpt2")
            e2 = ShardedCheckpointEngine(ckpt2, node_id=0, node_rank=0,
                                         world_size=1)
            try:
                assert e2.save_to_storage(10, small)
                assert e2.wait_for_persist(10, timeout=60)
                sh8 = {k: NamedSharding(mesh8, specs[k]) for k in state}
                e3 = ShardedCheckpointEngine(ckpt2, node_id=6,
                                             world_size=1)
                try:
                    back = e3.load_sharded(state, sh8)
                    assert back is not None and back[0] == 10
                    for k in state:
                        got = np.asarray(jax.device_get(back[1][k]))
                        assert _crc(got) == crcs[k], k
                        assert back[1][k].sharding.mesh.devices.size \
                            == 8
                finally:
                    e3.shm_handler.close(unlink=True)
                    e3.close()
            finally:
                e2.shm_handler.close(unlink=True)
                e2.close()
        finally:
            em.shm_handler.close(unlink=True)
            em.close()


# ------------------------------------------------------- persist-ack RPC


class TestPersistAckRPC:
    def test_ack_ledger_round_trip(self):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.job_master import JobMaster

        master = JobMaster(port=0, rdzv_timeout=2.0)
        master.prepare()
        try:
            clients = [MasterClient(master.addr, i) for i in range(3)]
            entry = {"crc32": 7, "bytes": 11,
                     "pieces": {"w::p0": {"crc32": 7, "index": [[0, 4]],
                                          "replica": 0}}}
            for i, c in enumerate(clients[:2]):
                c.report_persist_ack(4, 3, dict(entry, crc32=i))
            st = clients[0].persist_status(4, 3)
            assert st.acked == 2 and not st.complete
            clients[2].report_persist_ack(4, 3, dict(entry, crc32=2))
            st = clients[0].persist_status(4, 3)
            assert st.complete and set(st.shards) == {"0", "1", "2"}
            assert st.shards["1"]["pieces"]["w::p0"]["index"] == [[0, 4]]
            # a different writer-world is a different ledger key
            assert not clients[0].persist_status(4, 2).complete
            for c in clients:
                c.close()
        finally:
            master.stop()


# ------------------------------------------------------ typed wait results


class TestTypedWaitResults:
    def test_wait_for_persist_timeout_is_typed_and_journaled(
            self, tmp_ipc_dir, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR",
                           str(tmp_path / "journal"))
        eng = CheckpointEngine(str(tmp_path / "ckpt"))
        try:
            res = eng.wait_for_persist(5, timeout=0.3)
            assert isinstance(res, PersistWait)
            assert not res and res.kind == "timeout"
            assert res.persisted_step == -1 and res.step == 5
            events = [
                json.loads(line) for line in
                open(tmp_path / "journal" / "events.jsonl")
            ]
            t = [e for e in events if e["name"] == "ckpt_persist_timeout"]
            assert t and t[0]["what"] == "persist" and t[0]["step"] == 5
        finally:
            eng.close()

    def test_wait_for_persist_ok_is_truthy(self, tmp_ipc_dir, tmp_path):
        eng = CheckpointEngine(str(tmp_path / "ckpt"))
        try:
            state = {"w": np.arange(8, dtype=np.float32)}
            assert eng.save_to_storage(3, state)
            res = eng.wait_for_persist(3, timeout=60)
            assert res and res.kind == "ok" and res.persisted_step >= 3
        finally:
            eng.close()

    def test_restore_prefetch_timeout_outcome(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR",
                           str(tmp_path / "journal"))

        class GlacialStorage(PosixDiskStorage):
            def listdir(self, path):
                time.sleep(1.5)
                return []

        pf = RestorePrefetch(str(tmp_path / "ckpt"), node_id=0,
                             storage=GlacialStorage())
        assert pf.join(timeout=0.2) is None
        assert pf.outcome == "timeout"
        events = [
            json.loads(line) for line in
            open(tmp_path / "journal" / "events.jsonl")
        ]
        t = [e for e in events if e["name"] == "ckpt_persist_timeout"]
        assert t and t[0]["what"] == "restore_prefetch"
        pf._done.wait(5)  # let the thread finish before teardown

    def test_restore_prefetch_ok_outcome(self, tmp_path):
        pf = RestorePrefetch(str(tmp_path / "none"), node_id=0)
        assert pf.join(timeout=10) is None
        assert pf.outcome == "empty"


# ------------------------------------------------- chaos canned scenario


class TestShardedChaosScenario:
    def test_replay_identical_trail_and_bit_exact_restore(
            self, tmp_ipc_dir, tmp_path):
        from dlrover_tpu.chaos.scenario import run_sharded_scenario

        r1 = run_sharded_scenario(str(tmp_path / "run1"), seed=4242)
        r1.assert_invariants()
        # the storage_read injection point left trail evidence
        points = {f[0] for f in r1.trail["faults"]}
        assert points == {"storage_write", "storage_read"}
        assert any(e[0] == "ckpt_shard_rollback"
                   for e in r1.trail["recovery"])
        r2 = run_sharded_scenario(str(tmp_path / "run2"), seed=4242)
        r2.assert_invariants()
        assert r1.trail == r2.trail

    def test_storage_read_injection_unit(self, tmp_path):
        from dlrover_tpu import chaos

        path = str(tmp_path / "f.bin")
        STORAGE.write(b"\x00" * 64, path)
        chaos.install({"seed": 1, "faults": [
            {"point": "storage_read", "action": "bit_flip", "times": 1},
            # consulted only once rule 1's budget is spent (fire()
            # stops at the first firing rule), i.e. from read 2 on
            {"point": "storage_read", "action": "missing", "times": 1},
        ]})
        try:
            flipped = STORAGE.read(path)
            assert flipped != b"\x00" * 64  # transient, read-side
            assert open(path, "rb").read() == b"\x00" * 64  # disk clean
            with pytest.raises(FileNotFoundError):
                STORAGE.read(path)
            assert STORAGE.read(path) == b"\x00" * 64  # budget spent
        finally:
            chaos.uninstall()


# ------------------------------------------- gateway AOT cold start


class TestGatewayAotColdStart:
    def test_replica_ready_journals_compile_cache_evidence(
            self, tmp_path, monkeypatch):
        from dlrover_tpu.gateway.pool import ReplicaPool, ReplicaState
        from dlrover_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )
        from dlrover_tpu.serving.engine import InferenceEngine

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "cache"))
        monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR",
                           str(tmp_path / "journal"))
        cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                                n_kv_heads=2, d_model=32,
                                max_seq_len=32)
        params = init_params(cfg, jax.random.PRNGKey(0))

        def factory():
            return InferenceEngine(params, cfg, slots=2, max_len=32)

        pool = ReplicaPool(factory, on_done=lambda w, r: None,
                           on_orphans=lambda o: None)
        try:
            pool.ensure(1)
            deadline = time.time() + 120
            while time.time() < deadline:
                if pool.ready_replicas():
                    break
                time.sleep(0.1)
            assert pool.ready_replicas()
            pool.ensure(2)
            while time.time() < deadline:
                if len(pool.ready_replicas()) == 2:
                    break
                time.sleep(0.1)
            assert len(pool.ready_replicas()) == 2
        finally:
            pool.stop()
        events = [
            json.loads(line) for line in
            open(tmp_path / "journal" / "events.jsonl")
        ]
        ready = sorted(
            (e for e in events if e["name"] == "gateway_replica_ready"),
            key=lambda e: e["replica"],
        )
        assert len(ready) == 2
        assert all(e["aot"] for e in ready)
        # the first replica compiled+published; the second loaded it
        assert ready[0]["aot_hit"] is False
        assert ready[1]["aot_hit"] is True
        assert ready[1]["aot_seconds"] < 2.0
