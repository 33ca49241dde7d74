"""Splash attention (ops/splash_attention.py).

The TPU kernel cannot run on the CPU test mesh (it is compiled for a
described chip in tests/test_tpu_compile.py), so this file chooses the
dense reference explicitly — every test here runs inside
``reference_kernels()`` — and pins its mask semantics and the strategy
wiring.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import transformer as T
from dlrover_tpu.ops.flash_attention import reference_kernels
from dlrover_tpu.ops.splash_attention import (
    _dense_window,
    splash_attention,
)


@pytest.fixture(autouse=True)
def _dense_reference():
    with reference_kernels():
        yield


def _qkv(key, b=2, s=64, h=4, d=16):
    ks = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


class TestWindowMask:
    def test_no_window_matches_dense_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        a = splash_attention(q, k, v, causal=True)
        b = T.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )

    def test_window_limits_reach(self):
        """With window W, changing a key more than W positions back must
        not change the query's output; within W it must."""
        q, k, v = _qkv(jax.random.PRNGKey(1), s=32)
        W = 8
        out = splash_attention(q, k, v, causal=True, window=W)
        # perturb key at position 0; query at position 20 (> W away)
        k2 = k.at[:, 0].add(10.0)
        v2 = v.at[:, 0].add(10.0)
        out2 = splash_attention(q, k2, v2, causal=True, window=W)
        np.testing.assert_allclose(
            np.asarray(out[:, 20]), np.asarray(out2[:, 20]), rtol=1e-5
        )
        # query at position 5 (within W of key 0) must see the change
        assert not np.allclose(
            np.asarray(out[:, 5]), np.asarray(out2[:, 5])
        )

    def test_window_1_is_self_attention_only(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), s=16)
        out = splash_attention(q, k, v, causal=True, window=1)
        # each query attends only itself -> output == its own value row
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(v), rtol=1e-5, atol=1e-6
        )


class TestGqa:
    def test_grouped_kv_matches_repeated(self):
        """splash with G < H kv heads == dense with repeated kv."""
        b, s, h, g, d = 2, 32, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, g, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, g, d), jnp.float32)
        out = splash_attention(q, k, v, causal=True)
        kr = jnp.repeat(k, h // g, axis=2)
        vr = jnp.repeat(v, h // g, axis=2)
        ref = T.dense_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
        )

    def test_gqa_model_same_loss_as_dense(self):
        """A GQA model (kv_heads < heads) under native-GQA splash (the
        skipped KV repeat) matches the dense path numerically."""
        from dlrover_tpu.parallel import strategy as S

        cfg_d = dataclasses.replace(
            T.CONFIGS["tiny"], dtype="float32", n_kv_heads=2,
        )
        cfg_s = dataclasses.replace(cfg_d, attention="splash")
        params = T.init_params(cfg_d, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 17), 0, cfg_d.vocab_size
        )}
        strat = S.dp()
        strat.extra["native_gqa"] = True
        mesh = strat.build_mesh(jax.devices()[:2])  # batch 2: 1 row each
        a = float(jax.jit(T.make_loss_fn(cfg_d, S.dp(), mesh))(
            params, batch
        ))
        b = float(jax.jit(T.make_loss_fn(cfg_s, strat, mesh))(
            params, batch
        ))
        assert a == np.float32(b) or abs(a - b) < 1e-5


class TestStrategyWiring:
    def test_sliding_window_preset_trains(self):
        import optax

        from dlrover_tpu.parallel import strategy as S
        from dlrover_tpu.trainer import compile_train

        cfg = dataclasses.replace(T.CONFIGS["tiny"], dtype="float32")
        strat = S.sliding_window(window=16)
        mesh = strat.build_mesh()
        ct = compile_train(
            strategy=strat,
            mesh=mesh,
            loss_fn=T.make_loss_fn(cfg, strat, mesh),
            init_params_fn=lambda rng: T.init_params(cfg, rng),
            logical_params=T.logical_axes(cfg),
            optimizer=optax.adamw(1e-2),
        )
        state = ct.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (1, 8, 65), 0, cfg.vocab_size
        )}
        losses = []
        for _ in range(6):
            state, m = ct.step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_cfg_attention_splash(self):
        cfg = dataclasses.replace(
            T.CONFIGS["tiny"], dtype="float32",
            attention="splash", attention_window=8,
        )
        from dlrover_tpu.parallel import strategy as S

        strat = S.dp()
        mesh = strat.build_mesh(jax.devices()[:4])  # batch 4: 1 row each
        loss = T.make_loss_fn(cfg, strat, mesh)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size
        )}
        val = float(jax.jit(loss)(params, batch))
        assert math.isfinite(val)
        # window changes the loss vs full causal
        cfg_full = dataclasses.replace(cfg, attention_window=0)
        loss_full = T.make_loss_fn(cfg_full, strat, mesh)
        assert float(jax.jit(loss_full)(params, batch)) != val
