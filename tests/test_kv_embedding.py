"""Native KvVariable embedding runtime: correctness + toy bench.

Reference analog: tfplus/tfplus/kv_variable/kernels/kv_variable_test.cc and
the python op tests — lookup/insert, sparse Adam vs a numpy reference,
import/export round-trip, frequency filtering.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

from dlrover_tpu.embedding import KvEmbeddingTable


@pytest.fixture
def table():
    return KvEmbeddingTable(dim=8, num_slots=2, seed=42)


class TestLookup:
    def test_insert_and_stable_init(self, table):
        ids = np.array([5, 900000000000, -3, 5])
        out = table.lookup(ids)
        assert out.shape == (4, 8)
        assert len(table) == 3
        # same key -> same row, deterministic init
        np.testing.assert_array_equal(out[0], out[3])
        out2 = table.lookup(np.array([5]))
        np.testing.assert_array_equal(out2[0], out[0])
        # distinct keys get distinct init
        assert not np.array_equal(out[0], out[1])

    def test_missing_without_init_is_zero(self, table):
        out = table.lookup(np.array([123]), init_missing=False)
        np.testing.assert_array_equal(out, np.zeros((1, 8), np.float32))
        assert len(table) == 0

    def test_nd_ids(self, table):
        ids = np.arange(6).reshape(2, 3)
        out = table.lookup(ids)
        assert out.shape == (2, 3, 8)


class TestAdam:
    def _numpy_adam(self, w, g, m, v, lr, b1, b2, eps, step):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        w = w - lr * mhat / (np.sqrt(vhat) + eps)
        return w, m, v

    def test_matches_numpy_reference(self, table):
        ids = np.array([1, 2, 3])
        w0 = table.lookup(ids).copy()
        m = np.zeros_like(w0)
        v = np.zeros_like(w0)
        w = w0
        rng = np.random.default_rng(0)
        for step in range(1, 4):
            g = rng.standard_normal((3, 8)).astype(np.float32)
            table.apply_adam(ids, g, lr=0.01)
            w, m, v = self._numpy_adam(
                w, g, m, v, 0.01, 0.9, 0.999, 1e-8, step
            )
        np.testing.assert_allclose(
            table.lookup(ids), w, atol=1e-5, rtol=1e-5
        )

    def test_duplicate_ids_apply_sequentially(self, table):
        ids = np.array([7, 7])
        w0 = table.lookup(np.array([7]))[0].copy()
        g = np.stack([np.ones(8, np.float32), 2 * np.ones(8, np.float32)])
        table.apply_adam(ids, g, lr=0.1)
        w, m, v = w0, np.zeros(8), np.zeros(8)
        # both updates land, same bias-correction step
        w, m, v = self._numpy_adam(w, g[0], m, v, 0.1, 0.9, 0.999, 1e-8, 1)
        w, m, v = self._numpy_adam(w, g[1], m, v, 0.1, 0.9, 0.999, 1e-8, 1)
        np.testing.assert_allclose(
            table.lookup(np.array([7]))[0], w, atol=1e-5, rtol=1e-5
        )

    def test_group_lasso_prunes_rows(self, table):
        ids = np.array([11])
        table.lookup(ids)
        # a huge shrinkage threshold zeroes the row entirely
        table.apply_adam(ids, np.zeros((1, 8), np.float32), lr=1.0,
                         group_lasso=1e6)
        np.testing.assert_array_equal(
            table.lookup(ids), np.zeros((1, 8), np.float32)
        )

    def test_training_reduces_loss(self, table):
        """Toy regression: embeddings for 100 ids fit random targets."""
        rng = np.random.default_rng(1)
        ids = np.arange(100)
        targets = rng.standard_normal((100, 8)).astype(np.float32)

        def loss():
            return float(((table.lookup(ids) - targets) ** 2).mean())

        first = loss()
        for _ in range(200):
            g = 2 * (table.lookup(ids) - targets) / ids.size
            table.apply_adam(ids, g, lr=0.05)
        assert loss() < first * 0.05


class TestCheckpoint:
    def test_export_import_roundtrip_with_slots(self, table):
        ids = np.arange(50)
        table.lookup(ids)
        g = np.random.default_rng(2).standard_normal(
            (50, 8)
        ).astype(np.float32)
        table.apply_adam(ids, g, lr=0.01)
        snap = table.export()
        assert snap["keys"].size == 50

        restored = KvEmbeddingTable(dim=8, num_slots=2, seed=7)
        restored.import_(snap)
        assert len(restored) == 50
        np.testing.assert_array_equal(
            restored.lookup(ids, init_missing=False), table.lookup(ids)
        )
        # optimizer slots restored: identical next update
        g2 = np.ones((50, 8), np.float32)
        table.apply_adam(ids, g2, lr=0.01)
        restored.apply_adam(ids, g2, lr=0.01)
        np.testing.assert_allclose(
            restored.lookup(ids), table.lookup(ids), atol=1e-6
        )

    def test_frequency_filtering(self, table):
        hot = np.array([1, 2])
        cold = np.array([3])
        for _ in range(5):
            table.lookup(hot)
        table.lookup(cold)
        snap = table.export(min_freq=3)
        assert set(snap["keys"]) == {1, 2}

    def test_remove(self, table):
        table.lookup(np.arange(10))
        assert table.remove(np.array([0, 1, 99])) == 2
        assert len(table) == 8
        out = table.lookup(np.array([0]), init_missing=False)
        np.testing.assert_array_equal(out, np.zeros((1, 8), np.float32))


class TestIncrementalCheckpoint:
    def test_delta_tracks_only_changes(self, table):
        table.lookup(np.arange(10))
        table.clear_deltas()
        # update 3 rows, read 2 others: only updates are dirty
        table.apply_adam(np.array([1, 2, 3]), np.ones((3, 8), np.float32))
        table.lookup(np.array([7, 8]))
        delta = table.delta_export()
        assert sorted(delta["keys"].tolist()) == [1, 2, 3]
        assert delta["removed"].size == 0
        # clearing: the next delta is empty
        assert table.delta_export()["keys"].size == 0

    def test_delta_includes_removals(self, table):
        table.lookup(np.arange(5))
        table.clear_deltas()
        table.remove(np.array([0, 3]))
        delta = table.delta_export()
        assert sorted(delta["removed"].tolist()) == [0, 3]

    def test_base_plus_deltas_restores_exactly(self, tmp_path):
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        src = KvEmbeddingTable(dim=8, num_slots=2, seed=7)
        mgr = IncrementalCheckpointManager(
            src, str(tmp_path / "ckpt"), base_interval=100
        )
        rng = np.random.default_rng(0)
        src.lookup(np.arange(50))
        mgr.save()  # base-1
        for i in range(3):
            ids = rng.integers(0, 80, 20)  # some new, some existing
            src.apply_adam(ids, rng.normal(size=(20, 8)).astype(np.float32))
            src.remove(np.array([i]))
            mgr.save()  # delta-2..4
        dst = KvEmbeddingTable(dim=8, num_slots=2, seed=7)
        mgr2 = IncrementalCheckpointManager(dst, str(tmp_path / "ckpt"))
        assert mgr2.restore() == 4
        ref = src.export()
        got = dst.export()
        order_r = np.argsort(ref["keys"])
        order_g = np.argsort(got["keys"])
        np.testing.assert_array_equal(
            ref["keys"][order_r], got["keys"][order_g]
        )
        np.testing.assert_array_equal(
            ref["values"][order_r], got["values"][order_g]
        )
        np.testing.assert_array_equal(
            ref["slots"][order_r], got["slots"][order_g]
        )

    def test_failed_write_loses_nothing(self, tmp_path, monkeypatch):
        """A delta write that dies must not drop changes from the chain
        or leave a version gap."""
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        src = KvEmbeddingTable(dim=8, num_slots=2, seed=3)
        mgr = IncrementalCheckpointManager(src, str(tmp_path / "c"))
        src.lookup(np.arange(20))
        mgr.save()  # base-1
        src.apply_adam(np.array([4, 5]), np.ones((2, 8), np.float32))
        src.remove(np.array([9]))

        real_write = mgr._write
        calls = {"n": 0}

        def flaky(path, snap):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("disk full")
            real_write(path, snap)

        monkeypatch.setattr(mgr, "_write", flaky)
        with pytest.raises(OSError):
            mgr.save()
        # more changes after the failure, then a successful save
        src.apply_adam(np.array([5, 6]), np.ones((2, 8), np.float32))
        path = mgr.save()
        assert path.endswith("delta-2.npz")  # no version gap

        dst = KvEmbeddingTable(dim=8, num_slots=2, seed=3)
        mgr2 = IncrementalCheckpointManager(dst, str(tmp_path / "c"))
        assert mgr2.restore() == 2
        ref, got = src.export(), dst.export()
        o_r, o_g = np.argsort(ref["keys"]), np.argsort(got["keys"])
        np.testing.assert_array_equal(ref["keys"][o_r], got["keys"][o_g])
        np.testing.assert_array_equal(
            ref["values"][o_r], got["values"][o_g]
        )

    def test_merge_drops_rows_removed_later(self):
        from dlrover_tpu.embedding.kv_table import merge_deltas

        pending = {
            "keys": np.array([1, 2], np.int64),
            "values": np.ones((2, 4), np.float32),
            "slots": np.zeros((2, 8), np.float32),
            "freq": np.ones(2, np.uint32),
            "removed": np.empty(0, np.int64),
        }
        fresh = {
            "keys": np.empty(0, np.int64),
            "values": np.empty((0, 4), np.float32),
            "slots": np.empty((0, 8), np.float32),
            "freq": np.empty(0, np.uint32),
            "removed": np.array([2], np.int64),
        }
        out = merge_deltas(pending, fresh)
        # key 2 was removed after its pending export: replaying its stale
        # row would resurrect it
        assert out["keys"].tolist() == [1]
        assert out["removed"].tolist() == [2]

    def test_restore_refuses_orphan_deltas(self, tmp_path):
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        t = KvEmbeddingTable(dim=8, num_slots=2)
        mgr = IncrementalCheckpointManager(t, str(tmp_path / "c"))
        t.lookup(np.arange(4))
        mgr.save()
        t.apply_adam(np.array([1]), np.ones((1, 8), np.float32))
        p = mgr.save()
        # fabricate a gap: delta-2 exists, delta-3 missing, delta-4 orphan
        os.rename(p, p.replace("delta-2", "delta-4"))
        dst = KvEmbeddingTable(dim=8, num_slots=2)
        mgr2 = IncrementalCheckpointManager(dst, str(tmp_path / "c"))
        with pytest.raises(ValueError, match="later files exist"):
            mgr2.restore()
        # the chain was validated before any import: dst is untouched
        assert len(dst) == 0

    def test_enable_spill_twice_rejected(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "a.bin"))
        with pytest.raises(RuntimeError, match="already enabled"):
            table.enable_spill(str(tmp_path / "b.bin"))

    def test_removed_log_overflow_forces_base(self, tmp_path):
        """Overflowing the bounded removed log (deletions dropped) must
        break the delta chain loudly: the next save becomes a base and
        restore still matches the live table."""
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        t = KvEmbeddingTable(dim=4, num_slots=0)
        mgr = IncrementalCheckpointManager(
            t, str(tmp_path / "c"), base_interval=1000
        )
        t.lookup(np.arange(10))
        mgr.save()  # base-1
        # the per-shard cap is 2^16; one shard overflows well before
        # 17 * 2^16 total removals
        n = 17 * (1 << 16)
        ids = np.arange(n) + 1000
        t.lookup(ids, init_missing=True)
        t.remove(ids)
        assert t.delta_overflowed()
        path = mgr.save()
        assert "base-" in os.path.basename(path)
        assert not t.delta_overflowed()
        dst = KvEmbeddingTable(dim=4, num_slots=0)
        mgr2 = IncrementalCheckpointManager(dst, str(tmp_path / "c"))
        mgr2.restore()
        assert sorted(dst.export()["keys"]) == sorted(t.export()["keys"])

    def test_mark_dirty_reexports(self, table):
        table.lookup(np.arange(4))
        table.clear_deltas()
        table.mark_dirty(np.array([2, 99]))  # 99 absent: skipped
        delta = table.delta_export()
        assert delta["keys"].tolist() == [2]

    def test_deltas_are_smaller_than_base(self, tmp_path):
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        t = KvEmbeddingTable(dim=8, num_slots=2)
        mgr = IncrementalCheckpointManager(t, str(tmp_path / "c"))
        t.lookup(np.arange(1000))
        base = mgr.save()
        t.apply_adam(np.array([5]), np.ones((1, 8), np.float32))
        delta = mgr.save()
        assert os.path.getsize(delta) < os.path.getsize(base) / 10


class TestHybridStorage:
    def test_evict_and_fault_in_roundtrip(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "spill.bin"))
        vals = table.lookup(np.arange(100))  # freq 1 each
        hot = table.lookup(np.arange(10))  # freq 2 for [0, 10)
        spilled = table.evict(max_freq=1)
        assert spilled == 90
        assert table.disk_rows == 90
        assert len(table) == 100  # logical size unchanged
        # faulting in returns the exact spilled values
        back = table.lookup(np.arange(100))
        np.testing.assert_array_equal(back, vals)
        assert table.disk_rows == 0
        np.testing.assert_array_equal(hot, vals[:10])

    def test_update_faults_in(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "s.bin"))
        before = table.lookup(np.array([5]))
        table.evict(max_freq=10)
        assert table.disk_rows == 1
        table.apply_adam(np.array([5]), np.ones((1, 8), np.float32))
        assert table.disk_rows == 0
        after = table.lookup(np.array([5]))
        assert not np.array_equal(before, after)

    def test_export_sees_spilled_rows(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "s.bin"))
        vals = table.lookup(np.arange(20))
        table.evict(max_freq=10)
        assert table.disk_rows == 20
        snap = table.export()
        assert snap["keys"].size == 20
        order = np.argsort(snap["keys"])
        np.testing.assert_array_equal(snap["values"][order], vals)
        # export must not disturb the tiers
        assert table.disk_rows == 20

    def test_delta_export_sees_spilled_dirty_rows(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "s.bin"))
        table.lookup(np.arange(8))  # inserts are dirty
        table.evict(max_freq=10)
        delta = table.delta_export()
        assert sorted(delta["keys"].tolist()) == list(range(8))

    def test_remove_spilled_and_reuse(self, table, tmp_path):
        table.enable_spill(str(tmp_path / "s.bin"))
        table.lookup(np.arange(10))
        table.evict(max_freq=10)
        assert table.remove(np.arange(5)) == 5
        assert table.disk_rows == 5
        assert len(table) == 5
        # new inserts reuse freed slots; values still correct
        v = table.lookup(np.arange(100, 110))
        np.testing.assert_array_equal(v, table.lookup(np.arange(100, 110)))

    def test_incremental_ckpt_with_spill(self, tmp_path):
        """The spill tier composes with base+delta checkpoints."""
        from dlrover_tpu.embedding.kv_table import (
            IncrementalCheckpointManager,
        )

        src = KvEmbeddingTable(dim=8, num_slots=2, seed=11)
        src.enable_spill(str(tmp_path / "spill.bin"))
        mgr = IncrementalCheckpointManager(src, str(tmp_path / "ckpt"))
        src.lookup(np.arange(30))
        mgr.save()
        src.evict(max_freq=10)
        src.apply_adam(np.array([3]), np.ones((1, 8), np.float32))
        mgr.save()
        dst = KvEmbeddingTable(dim=8, num_slots=2, seed=11)
        mgr2 = IncrementalCheckpointManager(dst, str(tmp_path / "ckpt"))
        assert mgr2.restore() == 2
        ref, got = src.export(), dst.export()
        o_r, o_g = np.argsort(ref["keys"]), np.argsort(got["keys"])
        np.testing.assert_array_equal(
            ref["values"][o_r], got["values"][o_g]
        )


class TestConcurrencyStress:
    def test_concurrent_update_evict_delta_consistency(self, tmp_path):
        """Hammer the table from five threads (2x lookups/updates,
        removes, eviction sweeps, delta drains) and verify the end state is
        consistent: base + replayed deltas reconstruct exactly the live
        table, and no operation crashed."""
        import threading

        table = KvEmbeddingTable(dim=8, num_slots=2, seed=5)
        table.enable_spill(str(tmp_path / "spill.bin"))
        stop = threading.Event()
        errors: list = []
        deltas: list = []
        base = table.export()
        table.clear_deltas()

        def guard(fn):
            def run():
                try:
                    while not stop.is_set():
                        fn()
                except Exception as e:  # noqa: BLE001 - surfaced below
                    errors.append(e)
            return run

        rng_r = np.random.default_rng(2)

        def make_update(seed):
            # per-thread Generator: numpy Generators are not thread-safe
            rng = np.random.default_rng(seed)

            def update():
                ids = rng.integers(0, 5000, 64)
                table.lookup(ids)
                table.apply_adam(ids, np.ones((64, 8), np.float32))
            return update

        def remove():
            table.remove(rng_r.integers(0, 5000, 8))

        def evict():
            table.evict(max_freq=2, max_rows=256)

        def drain():
            deltas.append(table.delta_export())

        threads = [threading.Thread(target=guard(f), daemon=True)
                   for f in (make_update(1), make_update(11),
                             remove, evict, drain)]
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive(), "worker thread wedged"
        assert not errors, errors[:3]
        deltas.append(table.delta_export())  # final quiescent drain

        # replay base + deltas in order into a fresh table: must equal
        # the live table exactly (values, slots, and key set)
        from dlrover_tpu.embedding.kv_table import merge_deltas

        replayed = KvEmbeddingTable(dim=8, num_slots=2, seed=5)
        replayed.import_(base)
        for d in deltas:
            replayed.apply_delta(d)
        live = table.export()
        got = replayed.export()
        o_l = np.argsort(live["keys"])
        o_g = np.argsort(got["keys"])
        np.testing.assert_array_equal(
            live["keys"][o_l], got["keys"][o_g]
        )
        np.testing.assert_array_equal(
            live["values"][o_l], got["values"][o_g]
        )
        np.testing.assert_array_equal(
            live["slots"][o_l], got["slots"][o_g]
        )
        assert table.io_errors == 0
        # merge_deltas over the whole chain replays identically too
        merged = deltas[0]
        for d in deltas[1:]:
            merged = merge_deltas(merged, d)
        replayed2 = KvEmbeddingTable(dim=8, num_slots=2, seed=5)
        replayed2.import_(base)
        replayed2.apply_delta(merged)
        got2 = replayed2.export()
        o2 = np.argsort(got2["keys"])
        np.testing.assert_array_equal(
            live["keys"][o_l], got2["keys"][o2]
        )
        np.testing.assert_array_equal(
            live["values"][o_l], got2["values"][o2]
        )
        np.testing.assert_array_equal(
            live["slots"][o_l], got2["slots"][o2]
        )


class TestRecsysExample:
    def test_example_learns(self, tmp_path):
        """examples/train_recsys.py: sparse embedding + dense tower learns
        the synthetic signal (the DeepRec Criteo analog, BASELINE cfg 5)."""
        import json
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        result = tmp_path / "result.json"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "examples/train_recsys.py"),
             "--steps", "150", "--result-file", str(result),
             "--log-interval", "150"],
            env=env, cwd=repo, timeout=240, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        data = json.load(open(result))
        assert data["last_loss"] < 0.4
        assert data["table_rows"] > 1000


class TestBench:
    def test_toy_criteo_throughput(self, table):
        """Zipf-ish id stream; asserts only sanity, prints throughput."""
        import time

        rng = np.random.default_rng(3)
        ids = rng.zipf(1.3, size=50_000).astype(np.int64) % 1_000_000
        t0 = time.monotonic()
        out = table.lookup(ids)
        lookup_s = time.monotonic() - t0
        g = np.ones_like(out)
        t0 = time.monotonic()
        table.apply_adam(ids, g, lr=0.01)
        update_s = time.monotonic() - t0
        print(
            f"\nkv bench: {ids.size/lookup_s/1e6:.2f}M lookups/s, "
            f"{ids.size/update_s/1e6:.2f}M adam rows/s, "
            f"table={len(table)} rows"
        )
        assert lookup_s < 5 and update_s < 5


class TestOptimizerFamily:
    """The sparse-optimizer family beyond Adam (round-2 verdict Next #4).

    Reference: tfplus/tfplus/kv_variable/kernels/training_ops.cc (Adagrad,
    GroupAdam, GroupAdagrad, SparseGroupFtrl, RectifiedAdam) and the
    python wrappers under kv_variable/python/training/. Each kernel is
    checked against a numpy reference, the group variants against their
    pruning semantics, and the whole family under thread stress.
    """

    def _numpy_adagrad(self, w, g, a, lr, eps, l2):
        gd = g + l2 * w
        a = a + gd * gd
        w = w - lr * gd / (np.sqrt(a) + eps)
        return w, a

    def _numpy_ftrl(self, w, g, z, n, lr, l1, l2, beta):
        n_new = n + g * g
        sigma = (np.sqrt(n_new) - np.sqrt(n)) / lr
        z = z + g - sigma * w
        n = n_new
        w = np.where(
            np.abs(z) <= l1,
            0.0,
            -(z - np.sign(z) * l1) / ((beta + np.sqrt(n)) / lr + 2 * l2),
        ).astype(np.float32)
        return w, z, n

    def _numpy_radam(self, w, g, m, v, lr, b1, b2, eps, step, l2):
        gd = g + l2 * w
        m = b1 * m + (1 - b1) * gd
        v = b2 * v + (1 - b2) * gd * gd
        bc1 = 1 - b1 ** step
        bc2 = 1 - b2 ** step
        mhat = m / bc1
        rho_inf = 2 / (1 - b2) - 1
        rho_t = rho_inf - 2 * step * b2 ** step / bc2
        if rho_t > 4:
            rect = np.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                           / ((rho_inf - 4) * (rho_inf - 2) * rho_t))
            w = w - lr * rect * mhat / (np.sqrt(v / bc2) + eps)
        else:
            w = w - lr * mhat
        return w, m, v

    def test_adagrad_matches_numpy(self):
        table = KvEmbeddingTable(dim=8, num_slots=1, seed=9)
        ids = np.array([1, 2, 3])
        w = table.lookup(ids).copy()
        a = np.zeros_like(w)
        rng = np.random.default_rng(0)
        for _ in range(3):
            g = rng.standard_normal((3, 8)).astype(np.float32)
            table.apply_adagrad(ids, g, lr=0.1, l2=0.01)
            w, a = self._numpy_adagrad(w, g, a, 0.1, 1e-8, 0.01)
        np.testing.assert_allclose(table.lookup(ids), w,
                                   atol=1e-5, rtol=1e-5)

    def test_ftrl_matches_numpy_and_l1_sparsifies(self, table):
        ids = np.array([4, 5])
        w = table.lookup(ids).copy()
        z = np.zeros_like(w)
        n = np.zeros_like(w)
        rng = np.random.default_rng(1)
        for _ in range(4):
            g = rng.standard_normal((2, 8)).astype(np.float32)
            table.apply_ftrl(ids, g, lr=0.5, l1=0.1, l2=0.01)
            w, z, n = self._numpy_ftrl(w, g, z, n, 0.5, 0.1, 0.01, 1.0)
        np.testing.assert_allclose(table.lookup(ids), w,
                                   atol=1e-5, rtol=1e-5)
        # strong L1 zeroes coordinates whose |z| stays under the threshold
        big_l1 = KvEmbeddingTable(dim=8, num_slots=2, seed=9)
        big_l1.lookup(ids)
        big_l1.apply_ftrl(ids, np.full((2, 8), 1e-4, np.float32),
                          lr=0.5, l1=10.0)
        np.testing.assert_array_equal(
            big_l1.lookup(ids), np.zeros((2, 8), np.float32))

    def test_radam_matches_numpy_across_rectification_switch(self, table):
        """rho_t <= 4 early (momentum-SGD branch), > 4 later (rectified
        adaptive branch) — with beta2=0.9 the switch happens inside a
        handful of steps, covering both paths in one run."""
        ids = np.array([6])
        w = table.lookup(ids).copy()
        m = np.zeros_like(w)
        v = np.zeros_like(w)
        rng = np.random.default_rng(2)
        for step in range(1, 9):
            g = rng.standard_normal((1, 8)).astype(np.float32)
            table.apply_radam(ids, g, lr=0.01, beta2=0.9, l2=0.02,
                              step=step)
            w, m, v = self._numpy_radam(
                w, g, m, v, 0.01, 0.9, 0.9, 1e-8, step, 0.02)
        np.testing.assert_allclose(table.lookup(ids), w,
                                   atol=1e-5, rtol=1e-4)

    def test_group_variants_prune_rows(self):
        for opt, slots in (("group_adagrad", 1), ("group_ftrl", 2)):
            t = KvEmbeddingTable(dim=8, num_slots=slots, seed=3)
            ids = np.array([42])
            t.lookup(ids)
            t.apply(opt, ids, np.zeros((1, 8), np.float32), lr=1.0,
                    group_lasso=1e6)
            np.testing.assert_array_equal(
                t.lookup(ids), np.zeros((1, 8), np.float32))

    def test_slot_requirements_enforced(self):
        t0 = KvEmbeddingTable(dim=4, num_slots=0, seed=1)
        with pytest.raises(ValueError, match="num_slots"):
            t0.apply_adagrad(np.array([1]), np.zeros((1, 4), np.float32))
        t1 = KvEmbeddingTable(dim=4, num_slots=1, seed=1)
        for fn in (t1.apply_ftrl, t1.apply_radam, t1.apply_adam):
            with pytest.raises(ValueError, match="num_slots"):
                fn(np.array([1]), np.zeros((1, 4), np.float32))

    def test_apply_dispatch(self, table):
        ids = np.array([77])
        table.apply("radam", ids, np.ones((1, 8), np.float32))
        with pytest.raises(ValueError, match="unknown sparse optimizer"):
            table.apply("sgd", ids, np.ones((1, 8), np.float32))

    def test_family_under_thread_stress(self, table):
        """All four optimizers hammer overlapping ids concurrently with
        lookups and removals: no crash, no wedge, table stays sane."""
        import threading
        import time as _time

        stop = threading.Event()
        errors = []

        def guard(fn):
            def run():
                try:
                    while not stop.is_set():
                        fn()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
            return run

        shared = np.arange(64)

        def make(opt, seed):
            rng = np.random.default_rng(seed)  # Generators aren't
            # thread-safe: one per worker or the test flakes on its
            # own RNG instead of the locking under test

            def step():
                ids = rng.choice(shared, size=16)
                table.apply(opt, ids,
                            np.ones((16, 8), np.float32) * 0.01)
            return step

        reader_rng = np.random.default_rng(100)
        remover_rng = np.random.default_rng(101)

        def reader():
            table.lookup(reader_rng.choice(shared, size=32))

        def remover():
            table.remove(remover_rng.choice(shared, size=2))

        threads = [
            threading.Thread(target=guard(f), daemon=True)
            for f in (make("adam", 0), make("adagrad", 1),
                      make("ftrl", 2), make("radam", 3), reader, remover)
        ]
        for t in threads:
            t.start()
        _time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive(), "worker thread wedged"
        assert not errors, errors[:3]
        snap = table.export()
        assert np.isfinite(snap["values"]).all()
