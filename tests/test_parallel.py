"""Mesh / partition / strategy / train-step tests on the 8-device CPU mesh.

Mirrors the reference's parallel-group layout assertions
(atorch/atorch/tests/common_tests/distributed_test.py:160) as sharding-spec
assertions on a virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import transformer as T
from dlrover_tpu.parallel import strategy as S
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh, data_parallel_size
from dlrover_tpu.parallel.partition import spec_for
from dlrover_tpu.trainer import compile_train

CFG = T.CONFIGS["tiny"]


def _compile(strat, mesh):
    return compile_train(
        strategy=strat,
        mesh=mesh,
        loss_fn=lambda p, b: T.loss_fn(p, b, CFG),
        init_params_fn=lambda rng: T.init_params(CFG, rng),
        logical_params=T.logical_axes(CFG),
        optimizer=optax.adamw(1e-3),
    )


class TestMesh:
    def test_resolve_fill(self):
        assert MeshSpec({"data": -1}).resolved(8) == {"data": 8}
        assert MeshSpec({"fsdp": 4, "tensor": -1}).resolved(8) == {
            "fsdp": 4, "tensor": 2,
        }

    def test_canonical_order(self):
        sizes = MeshSpec({"tensor": 2, "data": 4}).resolved(8)
        assert list(sizes) == ["data", "tensor"]

    def test_errors(self):
        with pytest.raises(ValueError):
            MeshSpec({"data": 3}).resolved(8)
        with pytest.raises(ValueError):
            MeshSpec({"data": -1, "fsdp": -1}).resolved(8)
        with pytest.raises(ValueError):
            MeshSpec({"bogus": 2}).resolved(8)

    def test_build(self):
        mesh = build_mesh({"fsdp": 4, "tensor": 2})
        assert mesh.shape == {"fsdp": 4, "tensor": 2}
        assert data_parallel_size(mesh) == 4


class TestPartition:
    def test_hybrid_dcn_mesh(self):
        """dcn_axes build a hybrid (multi-slice) mesh; on CPU test
        devices the slice topology is emulated by layout."""
        mesh = build_mesh(
            MeshSpec(axes={"data": 4, "tensor": 2}, dcn_axes={"data": 2})
        )
        assert mesh.shape == {"data": 4, "tensor": 2}

    def test_hybrid_dcn_strategy_trains(self):
        """A strategy whose data axis spans DCN compiles and steps."""
        strat = S.Strategy(
            name="dcn_dp",
            mesh_axes={"data": 4, "tensor": 2},
            dcn_axes={"data": 2},
            rules=[["batch", ["data", "fsdp"]],
                   ["heads", "tensor"], ["mlp", "tensor"],
                   ["kv_heads", "tensor"], ["vocab", "tensor"]],
        )
        assert S.Strategy.from_json(strat.to_json()).dcn_axes == {"data": 2}
        mesh = strat.build_mesh()
        ct = _compile(strat, mesh)
        state = ct.init(jax.random.PRNGKey(0))
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (1, 4, 33), 0, CFG.vocab_size
        )
        _, metrics = ct.step(state, {"tokens": tok})
        assert np.isfinite(float(metrics["loss"]))

    def test_dcn_errors(self):
        with pytest.raises(ValueError, match="not among resolved"):
            build_mesh(MeshSpec(axes={"data": 8}, dcn_axes={"tensor": 2}))
        with pytest.raises(ValueError, match="does not divide"):
            build_mesh(MeshSpec(axes={"data": 8}, dcn_axes={"data": 3}))

    def test_missing_axis_replicates(self):
        mesh = build_mesh({"data": 8})
        spec = spec_for(("embed", "heads"), [("heads", "tensor")], mesh)
        assert spec == P()  # tensor axis absent -> fully replicated

    def test_axis_used_once(self):
        mesh = build_mesh({"fsdp": 8})
        spec = spec_for(
            ("embed", "mlp"), [("embed", "fsdp"), ("mlp", "fsdp")], mesh
        )
        assert spec == P("fsdp")  # second dim can't reuse the axis

    def test_multi_axis_dim(self):
        mesh = build_mesh({"data": 4, "fsdp": 2})
        spec = spec_for(("batch",), [("batch", ("data", "fsdp"))], mesh)
        assert spec == P(("data", "fsdp"))


class TestStrategies:
    @pytest.mark.parametrize("name,kwargs,expect_wq", [
        ("dp", {}, P()),
        ("fsdp", {}, P(None, "fsdp")),
        ("fsdp_tp", {"tensor_size": 2, "fsdp_size": 4},
         P(None, "fsdp", "tensor")),
        ("tp", {"tensor_size": 4}, P(None, None, "tensor")),
    ])
    def test_param_shardings(self, name, kwargs, expect_wq):
        strat = S.PRESETS[name](**kwargs)
        mesh = strat.build_mesh()
        ct = _compile(strat, mesh)
        state = ct.init(jax.random.PRNGKey(0))
        assert state.params["layers"]["wq"].sharding.spec == expect_wq

    def test_opt_state_follows_params(self):
        strat = S.fsdp(8)
        mesh = strat.build_mesh()
        ct = _compile(strat, mesh)
        state = ct.init(jax.random.PRNGKey(0))
        # adamw state: (ScaleByAdamState(count, mu, nu), ...) — mu follows
        mu = state.opt_state[0].mu
        assert mu["layers"]["wq"].sharding.spec == P(None, "fsdp")
        # the table shards over its embed dim, not the vocabulary: a
        # published vocab (50257) does not divide by a chip count
        assert mu["embed"].sharding.spec == P(None, "fsdp")

    def test_fsdp_shards_a_gpt2_vocabulary_over_four_devices(self):
        """50257 = 29 x 1733 divides by no mesh size: with a vocab rule
        the FSDP init program was refused on four devices."""
        import dataclasses

        cfg = dataclasses.replace(
            T.CONFIGS["tiny"], vocab_size=50257, variant="gpt2")
        strat = S.fsdp(4)
        mesh = strat.build_mesh(jax.devices()[:4])
        ct = compile_train(
            strategy=strat, mesh=mesh,
            loss_fn=T.make_loss_fn(cfg, strat, mesh),
            init_params_fn=lambda rng: T.init_params(cfg, rng),
            logical_params=T.logical_axes(cfg),
            optimizer=optax.adamw(1e-3),
        )
        state = jax.eval_shape(ct.init, jax.random.PRNGKey(0))
        assert state.params["embed"].shape == (50257, cfg.d_model)
        assert ct.state_shardings.params["embed"].spec == P(None, "fsdp")
        assert ct.state_shardings.params["lm_head"].spec == P("fsdp")

    def test_train_two_steps_loss_decreases(self):
        strat = S.fsdp(8)
        mesh = strat.build_mesh()
        ct = _compile(strat, mesh)
        state = ct.init(jax.random.PRNGKey(0))
        batch = jax.device_put(
            {"tokens": np.random.RandomState(0).randint(
                0, CFG.vocab_size, (1, 16, 33))},
            ct.batch_sharding,
        )
        state, m0 = ct.step(state, batch)
        state, m1 = ct.step(state, batch)
        assert float(m1["loss"]) < float(m0["loss"])
        assert int(state.step) == 2

    def test_serialization_roundtrip(self, tmp_path):
        s = S.fsdp_tp(tensor_size=2, fsdp_size=4, remat="dots")
        path = tmp_path / "strategy.json"
        s.save(str(path))
        s2 = S.Strategy.load(str(path))
        assert s2 == s

    def test_grad_accum_matches_large_batch(self):
        """accum=2 × micro=8 must match accum=1 × batch=16 (fixed global
        batch invariance — the ElasticTrainer contract). SGD so the update
        is linear in the gradient: Adam's first-step sign normalization
        would amplify bf16 forward noise to ±lr and mask the comparison."""
        strat = S.dp()
        mesh = strat.build_mesh()
        ct = compile_train(
            strategy=strat,
            mesh=mesh,
            loss_fn=lambda p, b: T.loss_fn(p, b, CFG),
            init_params_fn=lambda rng: T.init_params(CFG, rng),
            logical_params=T.logical_axes(CFG),
            optimizer=optax.sgd(0.1),
        )
        rng = np.random.RandomState(1)
        tokens = rng.randint(0, CFG.vocab_size, (16, 33))

        state_a = ct.init(jax.random.PRNGKey(7))
        batch_a = jax.device_put(
            {"tokens": tokens.reshape(1, 16, 33)}, ct.batch_sharding)
        state_a, _ = ct.step(state_a, batch_a)

        state_b = ct.init(jax.random.PRNGKey(7))
        batch_b = jax.device_put(
            {"tokens": tokens.reshape(2, 8, 33)}, ct.batch_sharding)
        state_b, _ = ct.step(state_b, batch_b)

        diffs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))),
            state_a.params, state_b.params,
        )
        # tolerance: bf16 forward noise × lr (reduction order differs
        # between the scanned and unscanned accumulation)
        assert max(jax.tree.leaves(diffs)) < 2e-4, diffs
    # slow tier (tier-1 envelope): among the heaviest bodies in this
    # file on XLA:CPU; core behavior stays covered by the lighter
    # tests in-tier. `pytest tests/` still runs it.
    @pytest.mark.slow

    def test_remat_same_loss(self):
        base = S.dp()
        remat = S.dp()
        remat.remat = "full"
        mesh = base.build_mesh()
        tokens = np.random.RandomState(2).randint(0, CFG.vocab_size, (1, 8, 33))
        losses = []
        for strat in (base, remat):
            ct = _compile(strat, mesh)
            state = ct.init(jax.random.PRNGKey(0))
            _, m = ct.step(
                state, jax.device_put({"tokens": tokens}, ct.batch_sharding))
            losses.append(float(m["loss"]))
        assert losses[0] == pytest.approx(losses[1], rel=1e-5)


class TestDryRun:
    def test_pick(self):
        from dlrover_tpu.parallel import pick_strategy

        def build(strat):
            mesh = strat.build_mesh()
            ct = _compile(strat, mesh)
            state_shape = jax.eval_shape(
                lambda: ct.init(jax.random.PRNGKey(0)))
            batch = {"tokens": jax.ShapeDtypeStruct((1, 8, 33), jnp.int32)}
            return ct.step, (state_shape, batch)

        best, reports = pick_strategy(build, [S.fsdp(8), S.dp()],
                                      objective="first_fit")
        assert best.name == "fsdp"
        assert all(r.ok for r in reports)

    def test_bad_candidate_reported(self):
        from dlrover_tpu.parallel import pick_strategy

        def build(strat):
            raise RuntimeError("boom")

        bad = S.dp()
        with pytest.raises(RuntimeError, match="no candidate"):
            pick_strategy(build, [bad])


class TestTransformerVariants:
    @pytest.mark.parametrize("variant", ["llama", "gpt2"])
    def test_forward_shapes(self, variant):
        import dataclasses

        cfg = dataclasses.replace(CFG, variant=variant)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        logits = T.forward(
            params, jnp.zeros((2, 16), jnp.int32), cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_gqa(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, n_kv_heads=2)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        assert params["layers"]["wk"].shape[2] == 2
        logits = T.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
        assert logits.shape == (1, 8, cfg.vocab_size)

    def test_param_count_property(self):
        params = T.init_params(CFG, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert actual == CFG.param_count


class TestAutoStrategy:
    def _pick(self, hbm_bytes, cfg=None, batch=8, **kwargs):
        import optax

        from dlrover_tpu.parallel.auto import auto_strategy

        cfg = cfg or T.CONFIGS["tiny"]
        example_batch = {
            "tokens": np.zeros((1, batch, cfg.max_seq_len + 1), np.int32)
        }
        return auto_strategy(
            loss_fn_for=lambda s, m: T.make_loss_fn(cfg, s, m),
            init_params_fn=lambda rng: T.init_params(cfg, rng),
            logical_params=T.logical_axes(cfg),
            optimizer=optax.adamw(1e-3),
            example_batch=example_batch,
            hbm_capacity_bytes=hbm_bytes,
            **kwargs,
        )

    # slow tier (tier-1 envelope): full multi-candidate compile cycle —
    # tens of seconds each on XLA:CPU. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_cached_auto_strategy_reuses_and_rekeys(self, tmp_path):
        """The load_strategy analog: the second call reloads the tuned
        pick (no search — instant, no reports), and a cache written for
        a different device count is ignored."""
        import json
        import time

        from dlrover_tpu.parallel.auto import cached_auto_strategy

        from dlrover_tpu.parallel.strategy import dp, zero2

        cache = str(tmp_path / "strategy.json")
        cfg = T.CONFIGS["tiny"]
        kwargs = dict(
            loss_fn_for=lambda s, m: T.make_loss_fn(cfg, s, m),
            init_params_fn=lambda rng: T.init_params(cfg, rng),
            logical_params=T.logical_axes(cfg),
            optimizer=optax.adamw(1e-3),
            example_batch={"tokens": np.zeros((1, 8, 33), np.int32)},
            hbm_capacity_bytes=0,
            # this test pins CACHING semantics (reuse/rekey), not
            # candidate breadth — the selection tests below cover that;
            # two candidates instead of five keeps the three searches
            # this test runs off the suite's critical path
            candidates=[dp(), zero2()],
        )
        s1, reports = cached_auto_strategy(cache, **kwargs)
        assert reports  # a real search ran
        t0 = time.monotonic()
        s2, reports2 = cached_auto_strategy(cache, **kwargs)
        assert time.monotonic() - t0 < 1.0  # reload, not re-search
        assert reports2 == []
        assert s2 == s1
        # a cache for a different workload fingerprint (other model,
        # batch, budget, or world size) must not be reused
        data = json.load(open(cache))
        data["fingerprint"] = "someone-elses-workload"
        json.dump(data, open(cache, "w"))
        s3, reports3 = cached_auto_strategy(cache, **kwargs)
        assert reports3  # searched again
        assert json.load(open(cache))["devices"] == 8  # rewritten
        # changed batch shape -> different fingerprint -> fresh search
        kwargs2 = dict(kwargs)
        kwargs2["example_batch"] = {
            "tokens": np.zeros((1, 16, 33), np.int32)
        }
        _, reports4 = cached_auto_strategy(cache, **kwargs2)
        assert reports4

    # slow tier (tier-1 envelope): full multi-candidate compile cycle —
    # tens of seconds each on XLA:CPU. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_ample_memory_prefers_dp(self):
        # fastest objective: either replicated-param strategy may win
        # (zero1 distributes the optimizer's elementwise work, so its
        # estimate can edge out dp on tiny models — the math is equal)
        strategy, reports = self._pick(hbm_bytes=0)  # 0 = unlimited
        assert strategy.name in ("dp", "zero1", "zero2")
        assert reports[0].ok
        # first_fit keeps the strict preference order: dp wins outright
        strategy, _ = self._pick(hbm_bytes=0, objective="first_fit")
        assert strategy.name == "dp"

    # slow tier (tier-1 envelope): full multi-candidate compile cycle —
    # tens of seconds each on XLA:CPU. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_tight_memory_falls_to_sharded(self):
        """With a param-dominated model, a budget between FSDP's sharded
        footprint and DP's replicated one forces the sharded pick."""
        import dataclasses

        cfg = dataclasses.replace(
            T.CONFIGS["tiny"], d_model=512, n_layers=4, d_ff=1024,
            vocab_size=8192, n_heads=8, n_kv_heads=8,
        )
        _, reports = self._pick(hbm_bytes=0, cfg=cfg, batch=8)
        by_name = {r.strategy_name: r for r in reports}
        dp_need = by_name["dp"].hbm_bytes
        fsdp_need = by_name["fsdp"].hbm_bytes
        assert fsdp_need < dp_need, (dp_need, fsdp_need)
        budget = (dp_need + fsdp_need) // 2
        strategy, _ = self._pick(hbm_bytes=budget, cfg=cfg, batch=8)
        assert strategy.name in ("fsdp", "fsdp_tp")


class TestStrategyNumericEquivalence:
    # slow tier for COMPILE COST only (four full strategy compiles,
    # ~20s; tests/test_pipeline.py::test_matches_dp_loss carries the
    # cross-layout equivalence in tier-1). The bound is the
    # reduction-order-tolerant one: different shardings reassociate the
    # bf16-compute reduce trees on XLA:CPU (measured 0.1-0.3% here),
    # while a genuinely wrong sharding shifts the loss by O(1).
    @pytest.mark.slow
    def test_same_loss_across_strategies(self):
        """DP/FSDP/TP/FSDP+TP are layout choices, not math choices: the
        same params and batch produce the same loss on every mesh
        (within the reduction-order bound)."""
        import optax
        from functools import partial

        from dlrover_tpu.parallel import strategy as S
        from dlrover_tpu.trainer.train_step import compile_train

        cfg = T.CONFIGS["tiny"]
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (1, 8, cfg.max_seq_len + 1), np.int32
        )
        losses = {}
        for strat in (S.dp(), S.fsdp(remat="none"), S.tp(tensor_size=2),
                      S.fsdp_tp(tensor_size=2, remat="none")):
            mesh = strat.build_mesh()
            compiled = compile_train(
                strategy=strat, mesh=mesh,
                loss_fn=T.make_loss_fn(cfg, strat, mesh),
                init_params_fn=lambda rng: T.init_params(cfg, rng),
                logical_params=T.logical_axes(cfg),
                optimizer=optax.adamw(1e-3),
            )
            state = compiled.init(jax.random.PRNGKey(0))
            batch = jax.device_put(
                {"tokens": tokens}, compiled.batch_sharding
            )
            _, metrics = compiled.step(state, batch)
            losses[strat.name] = float(jax.device_get(metrics["loss"]))
        from tests.test_pipeline import RTOL_CROSS_LAYOUT

        ref = losses["dp"]
        for name, loss in losses.items():
            assert loss == pytest.approx(ref, rel=RTOL_CROSS_LAYOUT), \
                losses


    def test_zero1_shards_opt_state_and_matches_dp(self):
        """ZeRO-1: Adam moments shard over the data axis (memory /8 on
        the 8-device mesh) while params stay replicated, and the losses
        match dp exactly — it is a layout choice, not an algorithm."""
        import dataclasses

        from dlrover_tpu.trainer.train_step import compile_train

        cfg = dataclasses.replace(T.CONFIGS["tiny"], dtype="float32")
        tokens = np.random.RandomState(5).randint(
            0, cfg.vocab_size, (1, 8, 33)
        )
        losses = {}
        shardings = {}
        for name in ("dp", "zero1"):
            strat = S.PRESETS[name]()
            mesh = strat.build_mesh()
            ct = compile_train(
                strategy=strat, mesh=mesh,
                loss_fn=T.make_loss_fn(cfg, strat, mesh),
                init_params_fn=lambda rng: T.init_params(cfg, rng),
                logical_params=T.logical_axes(cfg),
                optimizer=optax.adamw(1e-3),
            )
            state = ct.init(jax.random.PRNGKey(0))
            ls = []
            for _ in range(3):
                state, m = ct.step(
                    state,
                    jax.device_put({"tokens": tokens}, ct.batch_sharding),
                )
                ls.append(float(jax.device_get(m["loss"])))
            losses[name] = ls
            shardings[name] = ct.state_shardings
        assert losses["dp"] == pytest.approx(losses["zero1"], rel=1e-6)
        # params replicated in both; moments sharded only under zero1
        z_opt = [
            s.spec for s in jax.tree_util.tree_leaves(
                shardings["zero1"].opt_state,
                is_leaf=lambda x: hasattr(x, "spec"),
            )
        ]
        assert any(spec != P() for spec in z_opt), z_opt
        z_params = jax.tree_util.tree_leaves(
            shardings["zero1"].params,
            is_leaf=lambda x: hasattr(x, "spec"),
        )
        assert all(s.spec == P() for s in z_params)

    # slow tier (tier-1 envelope): full multi-candidate compile cycle —
    # tens of seconds each on XLA:CPU. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_zero2_matches_dp_and_reduce_scatters(self):
        """ZeRO-2: grads constrained to the moment layout — same losses
        as dp, and the compiled step shows the scatter pattern. XLA:CPU
        has no fused reduce-scatter op: it lowers the constraint as
        all-reduce + dynamic-slice (TPU fuses them), so the portable
        assertion is sharded-state machinery (all-gathers for the
        update) that plain dp's step does not contain."""
        import dataclasses

        from dlrover_tpu.trainer.train_step import compile_train

        cfg = dataclasses.replace(T.CONFIGS["tiny"], dtype="float32")
        tokens = np.random.RandomState(6).randint(
            0, cfg.vocab_size, (1, 8, 33)
        )
        losses = {}
        gathers = {}
        for name in ("dp", "zero2"):
            strat = S.PRESETS[name]()
            mesh = strat.build_mesh()
            ct = compile_train(
                strategy=strat, mesh=mesh,
                loss_fn=T.make_loss_fn(cfg, strat, mesh),
                init_params_fn=lambda rng: T.init_params(cfg, rng),
                logical_params=T.logical_axes(cfg),
                optimizer=optax.adamw(1e-3),
            )
            state = ct.init(jax.random.PRNGKey(0))
            batch = jax.device_put({"tokens": tokens}, ct.batch_sharding)
            hlo = ct.step.lower(state, batch).compile().as_text()
            gathers[name] = hlo.count("all-gather")
            ls = []
            for _ in range(3):
                state, m = ct.step(state, batch)
                ls.append(float(jax.device_get(m["loss"])))
            losses[name] = ls
        assert losses["dp"] == pytest.approx(losses["zero2"], rel=1e-6)
        assert gathers["dp"] == 0, gathers
        assert gathers["zero2"] > 0, gathers


class TestRematPolicies:
    # slow tier (tier-1 envelope): full multi-candidate compile cycle —
    # tens of seconds each on XLA:CPU. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_blockwise_ce_matches_full(self):
        """ce_chunks must not change the loss or its gradients — it only
        changes what lands in HBM."""
        import dataclasses

        cfg = dataclasses.replace(CFG, dtype="float32")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size
        )
        mask = (jax.random.uniform(jax.random.PRNGKey(2), (4, 33)) > 0.2)
        for batch in [{"tokens": tok}, {"tokens": tok, "mask": mask}]:
            ref, ref_g = jax.value_and_grad(
                lambda p: T.loss_fn(p, batch, cfg)
            )(params)
            for chunks in [4, 7, 128]:  # 7 -> falls back to a divisor
                cfg_c = dataclasses.replace(cfg, ce_chunks=chunks)
                got, got_g = jax.value_and_grad(
                    lambda p: T.loss_fn(p, batch, cfg_c)
                )(params)
                np.testing.assert_allclose(
                    float(got), float(ref), rtol=1e-5,
                    err_msg=f"chunks={chunks}",
                )
                jax.tree.map(
                    lambda a, b: np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
                    ),
                    got_g, ref_g,
                )

    def test_blockwise_ce_mup_scale(self):
        import dataclasses

        cfg = dataclasses.replace(
            CFG, dtype="float32", mup_base_width=32
        )
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size
        )}
        ref = T.loss_fn(params, batch, cfg)
        cfg_c = dataclasses.replace(cfg, ce_chunks=8)
        got = T.loss_fn(params, batch, cfg_c)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)

    def test_save_attn_same_loss_as_nothing(self):
        import dataclasses
        import optax
        from dlrover_tpu.trainer.train_step import compile_train

        tokens = np.random.RandomState(3).randint(
            0, T.CONFIGS["tiny"].vocab_size, (1, 8, 33)
        )
        losses = []
        for policy in ("nothing", "save_attn"):
            cfg = dataclasses.replace(
                T.CONFIGS["tiny"], remat_scan=True, remat_policy=policy
            )
            strat = S.dp()
            mesh = strat.build_mesh()
            ct = compile_train(
                strategy=strat, mesh=mesh,
                loss_fn=T.make_loss_fn(cfg, strat, mesh),
                init_params_fn=lambda rng: T.init_params(cfg, rng),
                logical_params=T.logical_axes(cfg),
                optimizer=optax.adamw(1e-3),
            )
            state = ct.init(jax.random.PRNGKey(0))
            state, m = ct.step(
                state,
                jax.device_put({"tokens": tokens}, ct.batch_sharding),
            )
            # a second step exercises gradients THROUGH the remat policy
            state, m = ct.step(
                state,
                jax.device_put({"tokens": tokens}, ct.batch_sharding),
            )
            losses.append(float(jax.device_get(m["loss"])))
        assert losses[0] == pytest.approx(losses[1], rel=2e-4), losses

    def test_offload_policy_grads(self):
        """offload_attn_ffn (activations to pinned host memory — the
        SelectiveOffloadingCheckpoint analog) must produce finite grads
        and the same loss as the non-offloaded policy."""
        import dataclasses

        tokens = {"tokens": jnp.asarray(np.random.RandomState(1).randint(
            0, 512, (2, 65)), jnp.int32)}
        losses = []
        for policy in ("save_attn_ffn", "offload_attn_ffn"):
            cfg = dataclasses.replace(
                T.CONFIGS["tiny"], remat_scan=True, remat_policy=policy)
            params = T.init_params(cfg, jax.random.PRNGKey(0))
            loss, g = jax.jit(jax.value_and_grad(
                lambda p: T.loss_fn(p, tokens, cfg=cfg)))(params)
            assert all(
                bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(g)
            )
            losses.append(float(loss))
        assert losses[0] == pytest.approx(losses[1], rel=1e-5), losses
    # slow tier (tier-1 envelope): among the heaviest bodies in this
    # file on XLA:CPU; core behavior stays covered by the lighter
    # tests in-tier. `pytest tests/` still runs it.
    @pytest.mark.slow

    def test_remat_interval_grad_parity(self):
        """Interleaved remat (remat_interval=2: only every other layer
        rematted, halving backward recompute) must produce the same
        gradients as per-layer remat, within the existing bf16 remat
        noise floor (measured: remat itself differs from no-remat by
        ~2.6e-3 on tiny)."""
        import dataclasses

        cfg1 = dataclasses.replace(
            T.CONFIGS["tiny"], remat_scan=True, remat_policy="nothing",
            n_layers=4,
        )
        cfg2 = dataclasses.replace(cfg1, remat_interval=2)
        cfg_bad = dataclasses.replace(cfg1, remat_interval=3)  # 4 % 3 != 0
        cfg_off = dataclasses.replace(cfg1, remat_scan=False,
                                      remat_interval=2)
        params = T.init_params(cfg1, jax.random.PRNGKey(0))
        tokens = {"tokens": jnp.asarray(np.random.RandomState(0).randint(
            0, 512, (2, 65)), jnp.int32)}
        g1 = jax.grad(lambda p: T.loss_fn(p, tokens, cfg=cfg1))(params)
        g2 = jax.grad(lambda p: T.loss_fn(p, tokens, cfg=cfg2))(params)
        diff = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(g2))
        )
        assert diff < 5e-3, diff
        with pytest.raises(ValueError, match="remat_interval"):
            T.loss_fn(params, tokens, cfg=cfg_bad)
        # interval without remat_scan must error, not silently ignore
        with pytest.raises(ValueError, match="remat_interval"):
            T.loss_fn(params, tokens, cfg=cfg_off)
