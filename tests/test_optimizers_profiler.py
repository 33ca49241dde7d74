"""AGD/WSAM optimizers + profiler utilities.

Reference analog: atorch optimizer unit tests (convergence on toy
problems) and AProfiler's flop accounting.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.optimizers import agd, wsam
from dlrover_tpu.utils import profiler


def _quadratic(params, batch=None):
    # min at x = 3, y = -1
    return (params["x"] - 3.0) ** 2 + 2.0 * (params["y"] + 1.0) ** 2


class TestAGD:
    def test_converges_on_quadratic(self):
        params = {"x": jnp.asarray(0.0), "y": jnp.asarray(0.0)}
        opt = agd(learning_rate=0.1)
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            g = jax.grad(_quadratic)(params)
            updates, state = opt.update(g, state)
            return optax.apply_updates(params, updates), state

        for _ in range(300):
            params, state = step(params, state)
        assert abs(float(params["x"]) - 3.0) < 1e-2
        assert abs(float(params["y"]) + 1.0) < 1e-2

    def test_first_step_matches_adam_direction(self):
        """Step 1 uses diff = grad, so the update direction equals Adam's
        sign(g)-scaled step for large gradients."""
        params = {"x": jnp.asarray(0.0)}
        opt = agd(learning_rate=0.1, delta=1e-12)
        state = opt.init(params)
        g = {"x": jnp.asarray(4.0)}
        updates, _ = opt.update(g, state)
        np.testing.assert_allclose(float(updates["x"]), -0.1, atol=1e-5)

    def test_trains_tiny_transformer_step(self):
        from functools import partial

        from dlrover_tpu.models import transformer as tfm

        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size
        )
        opt = agd(learning_rate=1e-3)
        state = opt.init(params)
        loss_fn = partial(tfm.loss_fn, cfg=cfg)

        @jax.jit
        def step(params, state):
            loss, g = jax.value_and_grad(loss_fn)(
                params, {"tokens": tokens}
            )
            updates, state = opt.update(g, state)
            return optax.apply_updates(params, updates), state, loss

        losses = []
        for _ in range(8):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestWSAM:
    def test_converges_and_prefers_flat_minima(self):
        init, step = wsam(
            _quadratic, optax.sgd(0.1), rho=0.05, gamma=0.5
        )
        params = {"x": jnp.asarray(0.0), "y": jnp.asarray(0.0)}
        state = init(params)
        jit_step = jax.jit(lambda p, s: step(p, s, None))
        for _ in range(200):
            params, state, loss = jit_step(params, state)
        assert abs(float(params["x"]) - 3.0) < 5e-2
        assert abs(float(params["y"]) + 1.0) < 5e-2

    def test_gamma_zero_equals_base(self):
        init, step = wsam(_quadratic, optax.sgd(0.1), rho=0.1, gamma=0.0)
        params = {"x": jnp.asarray(0.0), "y": jnp.asarray(0.0)}
        state = init(params)
        params2 = {"x": jnp.asarray(0.0), "y": jnp.asarray(0.0)}
        params, state, _ = step(params, state, None)
        g = jax.grad(_quadratic)(params2)
        expected = jax.tree.map(lambda p, gi: p - 0.1 * gi, params2, g)
        np.testing.assert_allclose(
            float(params["x"]), float(expected["x"]), atol=1e-6
        )


class TestAdam8bit:
    def test_states_are_int8_above_threshold(self):
        from dlrover_tpu.optimizers import adam_8bit

        params = {"w": jnp.zeros((5000,)), "b": jnp.zeros((3,))}
        opt = adam_8bit(1e-3)
        state = opt.init(params)
        assert state.mu["w"].codes.dtype == jnp.int8
        assert state.nu["w"].codes.dtype == jnp.int8
        # 20 blocks of 256 cover 5000 elements
        assert state.mu["w"].codes.shape == (20, 256)
        # small leaves (biases/norms) keep fp32 moments — quantizing a
        # (3,) leaf into a 256-wide block would cost memory and precision
        assert state.mu["b"].dtype == jnp.float32
        assert state.mu["b"].shape == (3,)

    def test_tracks_fp32_adam(self):
        """A few steps of 8-bit Adam stay close to exact Adam."""
        from dlrover_tpu.optimizers import adam_8bit

        params_a = {"x": jnp.asarray([0.0, 0.0])}
        params_b = {"x": jnp.asarray([0.0, 0.0])}
        opt_a = adam_8bit(0.05, block_size=256)
        opt_b = optax.adam(0.05)
        sa, sb = opt_a.init(params_a), opt_b.init(params_b)

        def grad(p):
            return {"x": 2 * (p["x"] - jnp.asarray([3.0, -1.0]))}

        step_a = jax.jit(
            lambda p, s: (lambda u, s2: (optax.apply_updates(p, u), s2))(
                *opt_a.update(grad(p), s)
            )
        )
        step_b = jax.jit(
            lambda p, s: (lambda u, s2: (optax.apply_updates(p, u), s2))(
                *opt_b.update(grad(p), s)
            )
        )
        for _ in range(100):
            params_a, sa = step_a(params_a, sa)
            params_b, sb = step_b(params_b, sb)
        np.testing.assert_allclose(
            np.asarray(params_a["x"]), np.asarray(params_b["x"]),
            atol=0.05,
        )

    def test_converges_on_tiny_transformer(self):
        from functools import partial

        from dlrover_tpu.models import transformer as tfm
        from dlrover_tpu.optimizers import adam_8bit

        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size
        )
        opt = adam_8bit(1e-2)
        state = opt.init(params)
        loss_fn = partial(tfm.loss_fn, cfg=cfg)

        @jax.jit
        def step(params, state):
            loss, g = jax.value_and_grad(loss_fn)(
                params, {"tokens": tokens}
            )
            updates, state = opt.update(g, state)
            return optax.apply_updates(params, updates), state, loss

        losses = []
        for _ in range(10):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestProfiler:
    def test_compiled_flops_matmul(self):
        a = jnp.ones((128, 128), jnp.float32)
        f = jax.jit(lambda a: a @ a)
        f(a)  # warm the cache
        flops = profiler.compiled_flops(f, a)
        # 2*n^3 matmul flops (allow backend fudge)
        assert flops == pytest.approx(2 * 128**3, rel=0.5)

    def test_profile_train_step(self):
        a = jnp.ones((64, 64), jnp.float32)

        @jax.jit
        def fake_step(state, batch):
            out = state @ batch
            return out, {"loss": out.sum()}

        state, stats = profiler.profile_train_step(
            fake_step, a, a, steps=5
        )
        assert stats.steps == 5
        assert stats.mean_s > 0
        assert stats.flops_per_step > 0

    def test_step_profiler_stats(self):
        prof = profiler.StepProfiler(
            flops_per_step=1e9, peak_flops=1e12, num_devices=1
        )
        import time as _time

        for _ in range(5):
            with prof.step():
                _time.sleep(0.001)
        s = prof.stats()
        assert s.steps == 5
        assert s.mean_s >= 0.001
        assert s.mfu is not None and 0 < s.mfu < 1


class TestFlopsBreakdown:
    """Analytic per-op-class FLOPs from the jaxpr (the AProfiler
    per-op formula table analog, atorch/utils/prof.py:482)."""

    def test_matmul_exact(self):
        from dlrover_tpu.utils.profiler import flops_breakdown

        a = jnp.zeros((64, 32))
        b = jnp.zeros((32, 48))
        bd = flops_breakdown(lambda a, b: a @ b, a, b)
        assert bd["dot_general"] == 2 * 64 * 32 * 48
        assert bd["total"] >= bd["dot_general"]

    def test_scan_multiplies_by_trip_count(self):
        from dlrover_tpu.utils.profiler import flops_breakdown

        def g(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

        bd = flops_breakdown(g, jnp.zeros((8, 32)), jnp.zeros((5, 32, 32)))
        assert bd["dot_general"] == 5 * 2 * 8 * 32 * 32

    def test_grad_counts_backward_dots(self):
        from dlrover_tpu.utils.profiler import flops_breakdown

        b = jnp.zeros((32, 48))
        bd = flops_breakdown(
            jax.grad(lambda a: jnp.sum(a @ b)), jnp.zeros((64, 32))
        )
        # fwd + the single dA backward dot (dB not needed: b is closed
        # over, not differentiated), each 2*64*32*48
        assert bd["dot_general"] == pytest.approx(2 * 2 * 64 * 32 * 48)

    def test_model_dots_near_analytic(self):
        from dlrover_tpu.models import transformer as T
        from dlrover_tpu.utils.profiler import flops_breakdown

        cfg = T.CONFIGS["tiny"]
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        tokens = {"tokens": jnp.zeros((2, 65), jnp.int32)}
        bd = flops_breakdown(
            lambda p: T.loss_fn(p, tokens, cfg=cfg), params
        )
        analytic = 2 * cfg.param_count * 2 * 64  # 2N per token forward
        # embedding gathers aren't dots, so the measured count sits a
        # bit under the parameter-based estimate
        assert 0.7 * analytic < bd["dot_general"] <= 1.1 * analytic
        assert bd["elementwise"] > 0 and bd["reduce"] > 0


class TestAdam4bit:
    def test_states_are_packed_nibbles(self):
        from dlrover_tpu.optimizers import adam_4bit

        params = {"w": jnp.zeros((5000,)), "b": jnp.zeros((3,))}
        opt = adam_4bit(1e-3)
        state = opt.init(params)
        # 40 blocks of 128, two codes per byte -> 64 bytes per block
        assert state.mu["w"].codes.dtype == jnp.int8
        assert state.mu["w"].codes.shape == (40, 64)
        # half the int8 footprint of adam_8bit for the same leaf
        assert state.mu["b"].dtype == jnp.float32

    def test_quantize_roundtrip_error_bounded(self):
        from dlrover_tpu.optimizers.low_bit import (
            _dequantize4,
            _quantize4,
        )

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
        for signed in (True, False):
            vals = jnp.abs(x) if not signed else x
            codes, scales = _quantize4(vals, 128, signed)
            back = _dequantize4(codes, scales, vals.shape, 128, signed)
            # quadratic codebook: coarse at the block max, fine near 0
            err = np.abs(np.asarray(back - vals))
            scale_of = np.repeat(np.asarray(scales), 128)[: vals.size]
            assert np.all(err <= 0.16 * scale_of + 1e-7)

    def test_tracks_fp32_adam(self):
        from dlrover_tpu.optimizers import adam_4bit

        params_a = {"x": jnp.zeros((256,))}
        params_b = {"x": jnp.zeros((256,))}
        target = jnp.asarray(
            np.random.default_rng(1).normal(size=(256,)).astype(
                np.float32)
        )
        opt_a = adam_4bit(0.05, min_quant_size=1)
        opt_b = optax.adam(0.05)
        sa, sb = opt_a.init(params_a), opt_b.init(params_b)

        def grad(p):
            return {"x": 2 * (p["x"] - target)}

        step_a = jax.jit(
            lambda p, s: (lambda u, s2: (optax.apply_updates(p, u), s2))(
                *opt_a.update(grad(p), s)
            )
        )
        step_b = jax.jit(
            lambda p, s: (lambda u, s2: (optax.apply_updates(p, u), s2))(
                *opt_b.update(grad(p), s)
            )
        )
        for _ in range(150):
            params_a, sa = step_a(params_a, sa)
            params_b, sb = step_b(params_b, sb)
        # both should be near the target; 4-bit tracks within tolerance
        assert float(jnp.abs(params_a["x"] - target).mean()) < 0.1
        np.testing.assert_allclose(
            np.asarray(params_a["x"]), np.asarray(params_b["x"]),
            atol=0.15,
        )


class TestPeaksTable:
    """One table keyed by device_kind; an unknown device is an error."""

    def test_v5e_row_holds_the_published_peaks(self):
        from dlrover_tpu.utils.profiler import PEAKS

        row = PEAKS["TPU v5 lite"]
        assert (row.bf16_flops, row.int8_ops) == (197e12, 393e12)
        assert (row.hbm_bytes, row.hbm_bps) == (16 * 10**9, 819e9)
        assert PEAKS["TPU v5e"] is row

    def test_unknown_device_kind_raises(self):
        from types import SimpleNamespace

        from dlrover_tpu.parallel.auto import device_hbm_bytes
        from dlrover_tpu.parallel.cost_model import HardwareSpec
        from dlrover_tpu.utils.profiler import (
            device_peak_flops,
            device_peaks,
        )

        unknown = SimpleNamespace(platform="tpu", device_kind="TPU v9",
                                  memory_stats=lambda: None)
        for fn in (device_peaks, device_peak_flops,
                   HardwareSpec.for_device, device_hbm_bytes):
            with pytest.raises(ValueError, match="peaks table"):
                fn(unknown)

    def test_known_tpu_and_cpu(self):
        from types import SimpleNamespace

        from dlrover_tpu.parallel.auto import device_hbm_bytes
        from dlrover_tpu.parallel.cost_model import HardwareSpec
        from dlrover_tpu.utils.profiler import device_peak_flops

        v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                              memory_stats=lambda: None)
        assert device_peak_flops(v5e) == 197e12
        assert device_hbm_bytes(v5e) == 16 * 10**9
        hw = HardwareSpec.for_device(v5e)
        assert (hw.peak_flops, hw.hbm_bps) == (197e12, 819e9)
        # the CPU test substrate has no peak: gauges off, no HBM check
        assert device_peak_flops() is None
        assert device_hbm_bytes() == 0
