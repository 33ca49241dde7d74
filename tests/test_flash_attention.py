"""``flash_attention``: always the TPU kernel, never a silent dense path.

The kernel itself is compiled for a described chip in
tests/test_tpu_compile.py; here the CPU mesh checks the dispatch rule:
without a TPU the kernel config is an error, and only a test that
enters ``reference_kernels()`` gets the dense einsum.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    reference_enabled,
    reference_kernels,
)
from dlrover_tpu.ops.splash_attention import splash_attention


def _qkv(b=2, s=256, h=2, d=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), dtype) for k in ks
    )


class TestNoSilentFallback:
    @pytest.mark.parametrize("kernel", [flash_attention, splash_attention])
    def test_kernel_off_tpu_is_an_error_not_dense(self, kernel):
        q, k, v = _qkv(s=128)
        with pytest.raises(Exception) as err:
            jax.block_until_ready(kernel(q, k, v, causal=True))
        assert not isinstance(err.value, AssertionError)

    def test_model_with_kernel_attention_off_tpu_is_an_error(self):
        from dlrover_tpu.parallel.strategy import dp

        cfg = dataclasses.replace(tfm.CONFIGS["tiny"], attention="flash")
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, cfg.max_seq_len + 1), jnp.int32)
        strat = dp()
        mesh = strat.build_mesh()
        with pytest.raises(Exception):
            jax.jit(tfm.make_loss_fn(cfg, strat, mesh))(
                params, {"tokens": tokens})

    def test_reference_is_scoped_to_the_context(self):
        assert not reference_enabled()
        with reference_kernels():
            assert reference_enabled()
        assert not reference_enabled()


class TestReferencePath:
    @pytest.mark.parametrize("causal", [True, False])
    def test_reference_is_dense(self, causal):
        q, k, v = _qkv(s=64)
        ref = tfm.dense_attention(q, k, v, causal=causal)
        with reference_kernels():
            out = flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_model_loss_flash_option(self):
        from dlrover_tpu.parallel.strategy import dp

        cfg = dataclasses.replace(tfm.CONFIGS["tiny"], attention="flash")
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, cfg.max_seq_len + 1), 0,
            cfg.vocab_size,
        )
        strat = dp()
        # one device: on a mesh the kernel runs per device under
        # shard_map, which wants the batch to divide by the data axes
        mesh = strat.build_mesh(jax.devices()[:1])
        with reference_kernels():
            loss_flash = jax.jit(tfm.make_loss_fn(cfg, strat, mesh))(
                params, {"tokens": tokens}
            )
        cfg_d = dataclasses.replace(cfg, attention="dense")
        loss_dense = jax.jit(tfm.make_loss_fn(cfg_d, strat, mesh))(
            params, {"tokens": tokens}
        )
        np.testing.assert_allclose(
            float(loss_flash), float(loss_dense), rtol=1e-5
        )
