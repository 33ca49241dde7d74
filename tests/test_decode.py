"""KV-cached decode equivalence with the training forward."""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.decode import forward_cached, generate, init_cache


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


class TestCachedForwardEquivalence:
    @pytest.mark.parametrize("name", ["tiny", "gpt2-small"])
    def test_prefill_matches_forward(self, name):
        cfg = _f32(
            dataclasses.replace(
                tfm.CONFIGS[name], n_layers=2, max_seq_len=64
            )
        )
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size
        )
        ref = tfm.forward(params, tokens, cfg)
        cache = init_cache(cfg, 2, 32)
        out, cache = forward_cached(params, tokens, cache, cfg)
        assert int(cache["pos"]) == 16
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
        )

    @pytest.mark.parametrize("name", [
        "tiny",
        # slow tier (tier-1 envelope): the gpt2-small variant compiles
        # +decodes ~21s on XLA:CPU; tiny covers the equivalence in-tier
        pytest.param("gpt2-small", marks=pytest.mark.slow),
    ])
    def test_incremental_matches_forward(self, name):
        """Prefill then one-token steps (pos > 0 — the path PPO decode
        actually runs, incl. gpt2's pos_embed dynamic slice) reproduce
        the full forward."""
        cfg = _f32(
            dataclasses.replace(
                tfm.CONFIGS[name], n_layers=2, max_seq_len=64
            )
        )
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size
        )
        ref = tfm.forward(params, tokens, cfg)

        cache = init_cache(cfg, 2, 16)
        out_p, cache = forward_cached(params, tokens[:, :4], cache, cfg)
        outs = [out_p]
        step = jax.jit(
            lambda t, c: forward_cached(params, t, c, cfg)
        )
        for i in range(4, 12):
            out_i, cache = step(tokens[:, i:i + 1], cache)
            outs.append(out_i)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=3e-4, rtol=3e-4
        )


class TestSlidingWindowDecode:
    # slow tier (tier-1 envelope): among the heaviest bodies in this
    # file on XLA:CPU; core behavior stays covered by the lighter
    # tests in-tier. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_windowed_decode_matches_windowed_forward(self):
        """A model trained with sliding-window attention must decode
        with the same mask — prefill+steps reproduce the windowed
        training forward, not the full-causal one."""
        cfg = _f32(
            dataclasses.replace(
                tfm.CONFIGS["tiny"], n_layers=2, max_seq_len=64,
                attention="splash", attention_window=4,
            )
        )
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size
        )
        from dlrover_tpu.ops.flash_attention import reference_kernels
        from dlrover_tpu.ops.splash_attention import make_splash_attention

        with reference_kernels():  # CPU: the windowed dense reference
            ref = tfm.forward(
                params, tokens, cfg,
                attention_fn=make_splash_attention(cfg.attention_window),
            )
        cache = init_cache(cfg, 2, 16)
        out_p, cache = forward_cached(params, tokens[:, :4], cache, cfg)
        outs = [out_p]
        for i in range(4, 12):
            out_i, cache = forward_cached(
                params, tokens[:, i:i + 1], cache, cfg
            )
            outs.append(out_i)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=3e-4, rtol=3e-4
        )
        # and it differs from the full-causal forward (the mask matters)
        full = tfm.forward(
            params, tokens, dataclasses.replace(cfg, attention="dense",
                                                attention_window=0)
        )
        assert not np.allclose(np.asarray(got), np.asarray(full),
                               atol=1e-3)

    def test_resolve_config_carries_strategy_window(self):
        """The sliding_window preset sets the window in strategy.extra;
        resolve_config must surface it so decode masks match training."""
        from dlrover_tpu.parallel import strategy as S

        cfg = tfm.CONFIGS["tiny"]
        assert cfg.attention_window == 0
        resolved = tfm.resolve_config(cfg, S.sliding_window(window=16))
        assert resolved.attention == "splash"
        assert resolved.attention_window == 16
        # and pipeline extras merge the same way
        resolved_pp = tfm.resolve_config(cfg, S.pipeline(pipeline_size=2))
        assert resolved_pp.pipeline_stages == 2


class TestMoeDecode:
    def _cfg(self):
        # generous capacity: drop patterns differ between full-sequence
        # routing (training) and per-step routing (decode), so exact
        # equivalence is only defined in the no-drop regime
        return _f32(
            dataclasses.replace(
                tfm.CONFIGS["tiny-moe"], max_seq_len=64,
                moe_capacity_factor=float(tfm.CONFIGS["tiny-moe"].moe_experts),
            )
        )

    def test_incremental_matches_forward(self):
        cfg = self._cfg()
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size
        )
        ref = tfm.forward(params, tokens, cfg)
        cache = init_cache(cfg, 2, 16)
        out_p, cache = forward_cached(params, tokens[:, :4], cache, cfg)
        outs = [out_p]
        step = jax.jit(lambda t, c: forward_cached(params, t, c, cfg))
        for i in range(4, 12):
            out_i, cache = step(tokens[:, i:i + 1], cache)
            outs.append(out_i)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=3e-4, rtol=3e-4
        )

    def test_generate_runs(self):
        cfg = tfm.CONFIGS["tiny-moe"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        out = generate(params, prompts, cfg, gen_len=4,
                       key=jax.random.PRNGKey(7))
        assert out.shape == (2, 7)
        assert (np.asarray(out[:, :3]) == np.asarray(prompts)).all()


class TestGenerate:
    def test_shapes_and_determinism(self):
        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        out = generate(params, prompts, cfg, gen_len=5,
                       key=jax.random.PRNGKey(7))
        assert out.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(out[:, :3]),
                                      np.asarray(prompts))
        out2 = generate(params, prompts, cfg, gen_len=5,
                        key=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))

    # slow tier (tier-1 envelope): among the heaviest bodies in this
    # file on XLA:CPU; core behavior stays covered by the lighter
    # tests in-tier. `pytest tests/` still runs it.
    @pytest.mark.slow
    def test_greedy_matches_uncached_argmax(self):
        """temperature=0 cached decode equals argmax over the full
        uncached forward at every step."""
        cfg = _f32(tfm.CONFIGS["tiny"])
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
        out = generate(params, prompts, cfg, gen_len=6,
                       key=jax.random.PRNGKey(0), temperature=0.0)
        # uncached greedy reference
        toks = prompts
        for _ in range(6):
            logits = tfm.forward(params, toks, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            toks = jnp.concatenate([toks, nxt.astype(jnp.int32)], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))

    def test_cached_is_faster_for_long_generation(self):
        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = jnp.zeros((4, 8), jnp.int32)
        gen = jax.jit(
            lambda p, k: generate(params, p, cfg, gen_len=48, key=k)
        )
        gen(prompts, jax.random.PRNGKey(0))  # compile

        from dlrover_tpu.rl.ppo import PPOConfig, sample

        ppo = PPOConfig(gen_len=48)
        ac = {"model": params, "value_head": jnp.zeros(cfg.d_model)}
        samp = jax.jit(lambda p, k: sample(ac, p, cfg, ppo, k))
        samp(prompts, jax.random.PRNGKey(0))

        def best_of(fn, n=3):
            times = []
            for i in range(n):
                t0 = time.monotonic()
                fn(prompts, jax.random.PRNGKey(i)).block_until_ready()
                times.append(time.monotonic() - t0)
            return min(times)

        cached_s = best_of(gen)
        uncached_s = best_of(samp)
        assert cached_s < uncached_s, (cached_s, uncached_s)


class TestSampling:
    """Serving-side sampler surface: top-k, nucleus, eos padding."""

    def test_top_k_one_equals_greedy(self):
        from dlrover_tpu.models.decode import sample_logits

        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        greedy = jnp.argmax(logits, axis=-1)
        sampled = sample_logits(logits, jax.random.PRNGKey(1),
                                temperature=1.0, top_k=1)
        np.testing.assert_array_equal(np.asarray(sampled),
                                      np.asarray(greedy))

    def test_top_p_masks_tail(self):
        from dlrover_tpu.models.decode import sample_logits

        # one dominant token (p ~ 0.97): tiny nucleus keeps only it
        logits = jnp.zeros((2, 8)).at[:, 3].set(5.0)
        for seed in range(5):
            out = sample_logits(logits, jax.random.PRNGKey(seed),
                                temperature=1.0, top_p=0.5)
            np.testing.assert_array_equal(np.asarray(out), 3)

    def test_temperature_zero_is_argmax(self):
        from dlrover_tpu.models.decode import sample_logits

        logits = jax.random.normal(jax.random.PRNGKey(2), (3, 16))
        out = sample_logits(logits, jax.random.PRNGKey(3), temperature=0)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.argmax(logits, axis=-1)))

    def test_generate_eos_pads_finished_rows(self):
        from dlrover_tpu.models.decode import generate

        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = jnp.ones((2, 4), jnp.int32)
        out = generate(params, prompts, cfg, gen_len=12,
                       key=jax.random.PRNGKey(1), temperature=1.0,
                       eos_id=7)
        gen = np.asarray(out[:, 4:])
        for row in gen:
            hits = np.where(row == 7)[0]
            if hits.size:  # everything after the first eos is eos
                assert np.all(row[hits[0]:] == 7)

    def test_generate_top_kp_runs_under_jit(self):
        from functools import partial

        from dlrover_tpu.models.decode import generate

        cfg = tfm.CONFIGS["tiny"]
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        fn = jax.jit(partial(generate, cfg=cfg, gen_len=6,
                             temperature=0.8, top_k=16, top_p=0.9))
        out = fn(params, jnp.ones((2, 3), jnp.int32),
                 key=jax.random.PRNGKey(4))
        assert out.shape == (2, 9)
        assert np.all(np.asarray(out) >= 0)
        assert np.all(np.asarray(out) < cfg.vocab_size)
