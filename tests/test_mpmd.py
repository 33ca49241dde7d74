"""MPMD pipeline runtime (parallel/mpmd.py) on the 8-device CPU mesh.

The ISSUE-10 acceptance surface: per-stage programs must compute what
the SPMD pipeline computes (within the reduction-order bound
``RTOL_CROSS_LAYOUT`` of tests/test_pipeline.py), the host 1F1B
schedule's measured bubble must sit at the ``(P-1)/(M+P-1)`` bound, a
single-stage failure must recompile ONLY that stage (journal-pinned
``pipeline_stage_compile`` trail), and the per-stage weight update must
actually shard the optimizer state ZeRO-style over the stage submesh's
data axis.
"""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import transformer as T
from dlrover_tpu.parallel import strategy as S
from dlrover_tpu.parallel.mpmd import (
    MpmdTrain,
    choose_schedule,
    split_params,
    stage_op_schedule,
)
from dlrover_tpu.parallel.pipeline import bubble_fraction
from tests.test_pipeline import RTOL_CROSS_LAYOUT

CFG = dataclasses.replace(T.CONFIGS["tiny"], n_layers=4, dtype="float32")
SEQ = 32


def _tokens(key, b=16):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(key), (1, b, SEQ + 1), 0,
                           CFG.vocab_size)
    )


def _mpmd(optimizer=None, microbatches=4, accum=1, cfg=CFG):
    return MpmdTrain(
        cfg, S.mpmd(pipeline_size=2), optimizer or optax.sgd(1e-2),
        num_stages=2, microbatches=microbatches, seq=SEQ, step_batch=16,
        accum=accum,
    )


@pytest.fixture()
def aot_dir(tmp_path, monkeypatch):
    """Hermetic per-test compile-cache dir (the runtime's programs all
    ride load_or_compile)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "aot"))
    monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR", str(tmp_path / "jr"))
    return tmp_path


def _stage_compile_events(tmp_path):
    path = tmp_path / "jr" / "events.jsonl"
    if not os.path.exists(path):
        return []
    return [json.loads(line) for line in open(path)
            if json.loads(line)["name"] == "pipeline_stage_compile"]


class TestStageSplit:
    def test_split_covers_every_param(self):
        params = T.init_params(CFG, jax.random.PRNGKey(0))
        stages = split_params(params, 2)
        assert "embed" in stages[0] and "embed" not in stages[1]
        assert "lm_head" in stages[1] and "lm_head" not in stages[0]
        assert "ln_f" in stages[1]
        for tree in stages:
            for leaf in jax.tree_util.tree_leaves(tree["layers"]):
                assert leaf.shape[0] == CFG.n_layers // 2
        # every layer row lands in exactly one stage
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(s["layers"]["wq"])
                            for s in stages]),
            np.asarray(params["layers"]["wq"]),
        )

    def test_indivisible_layers_raise(self):
        params = T.init_params(CFG, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="divisible"):
            split_params(params, 3)

    def test_single_stage_rejected(self):
        params = T.init_params(CFG, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=">= 2 stages"):
            split_params(params, 1)

    def test_moe_rejected(self):
        cfg = dataclasses.replace(T.CONFIGS["tiny-moe"], n_layers=4)
        with pytest.raises(NotImplementedError, match="MoE"):
            MpmdTrain(cfg, S.mpmd(2), optax.sgd(1e-2), num_stages=2,
                      microbatches=4, seq=SEQ, step_batch=16)

    def test_interleave_rejected(self):
        strat = S.mpmd(2)
        strat.extra["pipeline_interleave"] = 2
        with pytest.raises(NotImplementedError, match="1F1B"):
            MpmdTrain(CFG, strat, optax.sgd(1e-2), num_stages=2,
                      microbatches=4, seq=SEQ, step_batch=16)


class TestScheduleShape:
    """Pure host properties of the canonical 1F1B order — no jax."""

    @pytest.mark.parametrize("P,M", [(2, 2), (2, 4), (4, 4), (4, 8)])
    def test_op_counts_and_order(self, P, M):
        ops = stage_op_schedule(P, M)
        for s, stage_ops in enumerate(ops):
            assert len(stage_ops) == 2 * M
            fwds = [m for kind, m in stage_ops if kind == "F"]
            bwds = [m for kind, m in stage_ops if kind == "B"]
            assert fwds == list(range(M)) and bwds == list(range(M))
            # 1F1B memory bound: in-flight stashed activations never
            # exceed the warmup depth + 1
            depth = 0
            for kind, _ in stage_ops:
                depth += 1 if kind == "F" else -1
                assert depth <= min(M, P - 1 - s) + 1

    def test_last_stage_strictly_alternates(self):
        ops = stage_op_schedule(4, 8)[-1]
        kinds = [k for k, _ in ops]
        assert kinds == ["F", "B"] * 8


class TestNumerics:
    def test_matches_spmd_pipeline_loss(self, aot_dir):
        """ACCEPTANCE: MPMD loss == the SPMD pipeline's on the same
        seed/geometry, two consecutive steps (the second pins the
        ZeRO-sharded update path too), within RTOL_CROSS_LAYOUT."""
        from dlrover_tpu.trainer import compile_train

        b1, b2 = _tokens(42), _tokens(43)
        mt = _mpmd()
        state = mt.init(jax.random.PRNGKey(0))
        got = []
        for b in (b1, b2):
            batch = {"tokens": jax.device_put(b, mt.batch_sharding)}
            state, m = mt.step(state, batch)
            got.append(float(jax.device_get(m["loss"])))

        strat = S.pipeline(pipeline_size=2, data_size=4)
        mesh = strat.build_mesh()
        ct = compile_train(
            strategy=strat, mesh=mesh,
            loss_fn=T.make_loss_fn(CFG, strat, mesh),
            init_params_fn=lambda rng: T.init_params(CFG, rng),
            logical_params=T.logical_axes(CFG),
            optimizer=optax.sgd(1e-2),
        )
        sd = ct.init(jax.random.PRNGKey(0))
        ref = []
        for b in (b1, b2):
            sd, m = ct.step(sd, jax.device_put({"tokens": b},
                                               ct.batch_sharding))
            ref.append(float(jax.device_get(m["loss"])))
        assert got[0] == pytest.approx(ref[0], rel=RTOL_CROSS_LAYOUT)
        assert got[1] == pytest.approx(ref[1], rel=RTOL_CROSS_LAYOUT)

    def test_trains_and_bubble_at_1f1b_bound(self, aot_dir):
        mt = _mpmd(optax.adamw(1e-2))
        state = mt.init(jax.random.PRNGKey(0))
        losses = []
        for i in range(6):
            batch = {"tokens": jax.device_put(_tokens(i),
                                              mt.batch_sharding)}
            state, m = mt.step(state, batch)
            losses.append(float(jax.device_get(m["loss"])))
        assert losses[-1] < losses[0]
        # the measured schedule bubble sits AT the 1F1B bound — the
        # dependency-driven executor leaves no extra idle ticks
        assert mt.last_bubble_frac == pytest.approx(
            bubble_fraction(2, 4), abs=1e-9)
        assert mt.last_bubble_frac <= mt.bubble_bound + 1e-9
        assert int(state.step) == 6

    def test_accum_rounds_match_single_round(self, aot_dir):
        """[2, 16, S] with accum=2 equals one [1, 32, S] dp-style global
        batch halved — pin the accumulation scale: two rounds of M=4
        average like one round of the doubled batch."""
        tok = _tokens(7, b=32)[0]
        mt = _mpmd(accum=2)
        state = mt.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.device_put(
            tok.reshape(2, 16, SEQ + 1), mt.batch_sharding)}
        _, m = mt.step(state, batch)
        mt8 = MpmdTrain(
            CFG, S.mpmd(2), optax.sgd(1e-2), num_stages=2,
            microbatches=8, seq=SEQ, step_batch=32, accum=1,
        )
        state8 = mt8.init(jax.random.PRNGKey(0))
        batch8 = {"tokens": jax.device_put(
            tok.reshape(1, 32, SEQ + 1), mt8.batch_sharding)}
        _, m8 = mt8.step(state8, batch8)
        assert float(m["loss"]) == pytest.approx(float(m8["loss"]),
                                                 rel=1e-6)


class TestPerStageCache:
    def test_single_stage_failure_recompiles_only_that_stage(
            self, aot_dir):
        """ACCEPTANCE: evict one stage's artifacts (= its replacement
        host lost them) and rebuild — the journal shows cold
        ``pipeline_stage_compile`` entries for EXACTLY that stage while
        the other P−1 stages hit the cache."""
        from dlrover_tpu.parallel import compile_cache as cc

        _mpmd()  # cold build, publishes all stage programs
        cold = _stage_compile_events(aot_dir)
        assert len(cold) == 5 and all(not e["hit"] for e in cold)
        evicted = glob.glob(
            os.path.join(cc.default_local_dir(), "*pp0of2*"))
        assert len(evicted) == 3  # fwd/bwd/update of stage 0
        for f in evicted:
            os.unlink(f)
        mt = _mpmd()
        events = _stage_compile_events(aot_dir)[len(cold):]
        cold_stages = {e["stage"] for e in events if not e["hit"]}
        warm_stages = {e["stage"] for e in events if e["hit"]}
        assert cold_stages == {0}
        assert warm_stages == {1}
        assert mt.stages[0].cache_misses == 3
        assert mt.stages[1].cache_misses == 0
        # per-stage keys carry stage index + chunk config + phase
        assert any("pp0of2v1fwd" in e["key"] for e in events)

    def test_warm_build_beats_cold_by_stage_count(self, aot_dir):
        """Per-stage warm load ≤ 1/P of the cold compile (acceptance
        bound, generous: measured ~16x on this host)."""
        import time

        t0 = time.monotonic()
        _mpmd()
        cold_s = time.monotonic() - t0
        t0 = time.monotonic()
        mt = _mpmd()
        warm_s = time.monotonic() - t0
        assert mt.cache_hit
        assert warm_s <= cold_s / 2

    def test_rebuild_stage_reloads_from_cache(self, aot_dir):
        mt = _mpmd()
        before = len(_stage_compile_events(aot_dir))
        prog = mt.rebuild_stage(1)
        events = _stage_compile_events(aot_dir)[before:]
        assert {e["stage"] for e in events} == {1}
        assert all(e["hit"] for e in events)
        assert prog.cache_misses == 0


class TestZeroSharding:
    def test_opt_state_shards_over_stage_data_axis(self, aot_dir):
        """ACCEPTANCE: optimizer-state bytes per device ÷data-axis vs
        replicated, with the adamw moments actually laid out
        P('data')."""
        mt = _mpmd(optax.adamw(1e-2))
        state = mt.init(jax.random.PRNGKey(0))
        from jax.sharding import PartitionSpec as P

        sharded_leaves = [
            leaf for leaf in jax.tree_util.tree_leaves(
                state.stages[0]["opt_state"])
            if leaf.sharding.spec == P("data")
        ]
        assert sharded_leaves, "no ZeRO-sharded moment leaves"
        for leaf in sharded_leaves:
            shard = leaf.addressable_shards[0].data
            assert shard.size * mt.data_size == leaf.size
        for s in range(mt.num_stages):
            by = mt.opt_bytes[s]
            # moments dominate: per-device bytes land near 1/data_size
            assert by["sharded"] < by["replicated"] / 2
        # params stay replicated (ZeRO-1: layout of the STATE only)
        for leaf in jax.tree_util.tree_leaves(state.stages[0]["params"]):
            assert leaf.sharding.spec == P()


class TestScheduleGate:
    def test_lm_head_heavy_config_prefers_mpmd(self):
        """Real configs are heterogeneous (stage 0 embeds, the last
        stage pays the LM-head matmul), so the cost-model gate picks
        MPMD over the lockstep roll."""
        kind, ests = choose_schedule(
            T.CONFIGS["gpt2-small"], num_stages=4, step_batch=32,
            seq=512,
        )
        assert kind == "mpmd"
        assert ests["mpmd"] < ests["spmd"]

    def test_deep_interleave_on_uniform_stages_keeps_spmd(self):
        """A deep interleaved roll on a near-uniform stage set beats
        plain-1F1B MPMD — the gate must keep SPMD there."""
        cfg = dataclasses.replace(
            T.CONFIGS["tiny"], n_layers=32, vocab_size=64, d_model=256)
        kind, ests = choose_schedule(
            cfg, num_stages=4, step_batch=8, seq=64, interleave=8,
        )
        assert kind == "spmd"
        assert ests["spmd"] <= ests["mpmd"]
