"""Splash attention: sparse-mask Pallas attention (causal / local window).

Reference analog: none — SURVEY.md §5.7 marks long-context attention a
capability gap the TPU build must fill natively; splash attention is the
TPU-idiomatic sparse-mask kernel (jax.experimental.pallas.ops.tpu.
splash_attention). Beyond the dense-causal flash kernel it skips whole
blocks that the mask zeroes, which makes sliding-window ("local")
attention pay only for the window: at seq S with window W the work drops
from O(S^2/2) to O(S*W).

Exposed through the same AttentionFn interface the transformer uses
(``[B, S, H, D]``, ``causal`` kwarg), selected via
``TransformerConfig.attention = "splash"`` with an optional
``attention_window``. Always the kernel: off-TPU the Pallas lowering
raises, and CPU tests that need the (windowed) dense einsum enter
``ops.flash_attention.reference_kernels()``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.flash_attention import reference_enabled


def _dense_window(q, k, v, *, causal: bool, window: int) -> jax.Array:
    """Reference path: dense attention with an optional local window.

    The window==0 case delegates to the canonical dense_attention so
    there is exactly one full-causal softmax implementation to drift.
    """
    from dlrover_tpu.models.transformer import dense_attention

    if window <= 0:
        return dense_attention(q, k, v, causal=causal)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * scale
    s_q, s_k = q.shape[1], k.shape[1]
    q_pos = jnp.arange(s_q)[:, None]
    k_pos = jnp.arange(s_k)[None, :]
    mask = q_pos - k_pos < window
    if causal:
        mask &= q_pos >= k_pos
    else:
        mask &= k_pos - q_pos < window
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def splash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window: int = 0) -> jax.Array:
    """Sparse-mask attention; [B, S, H, D] like dense_attention.

    ``window > 0`` restricts each query to the last ``window`` keys
    (sliding-window / local attention); the kernel skips fully-masked
    blocks, so long sequences pay O(S * window).

    Grouped-query attention is native: k/v may carry fewer heads than q
    (H divisible by G) — the MQA kernel reads the shared KV directly
    instead of the repeat-to-H path, cutting KV memory traffic by H/G.
    """
    n_rep = q.shape[2] // k.shape[2]
    if reference_enabled():
        if n_rep > 1:
            k = jnp.repeat(k, n_rep, axis=2)
            v = jnp.repeat(v, n_rep, axis=2)
        return _dense_window(q, k, v, causal=causal, window=window)

    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    B, S, H, D = q.shape
    if window > 0:
        # LocalMask allows keys in [q - left, q + right]
        base = sm.LocalMask(
            (S, S), (window - 1, 0 if causal else window - 1), 0,
        )
    elif causal:
        base = sm.CausalMask((S, S))
    else:
        base = sm.FullMask((S, S))
    # 512 blocks + fused bwd measured fastest on v5e across seq 1k-8k
    # (vs the 128 defaults: 51.6ms -> 13.8ms causal fwd+bwd at 8k, and
    # 1.2-1.5x faster than the tuned dense-causal flash kernel); gcd
    # keeps any 128-multiple sequence divisible
    b = math.gcd(S, 512)
    blocks = sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True,
    )
    scale = 1.0 / math.sqrt(D)

    if n_rep > 1:
        # GQA: one MQA kernel per kv group, vmapped over (batch, group)
        G = k.shape[2]
        mask = sm.MultiHeadMask([base for _ in range(n_rep)])
        kernel = sk.make_splash_mqa_single_device(mask=mask,
                                                  block_sizes=blocks)
        qg = (q * scale).transpose(0, 2, 1, 3).reshape(B, G, n_rep, S, D)
        kg = k.transpose(0, 2, 1, 3)  # [B, G, S, D]
        vg = v.transpose(0, 2, 1, 3)
        out = jax.vmap(jax.vmap(kernel))(qg, kg, vg)  # [B, G, n_rep, S, D]
        return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    mask = sm.MultiHeadMask([base for _ in range(H)])
    kernel = sk.make_splash_mha_single_device(mask=mask,
                                              block_sizes=blocks)
    # [B, S, H, D] -> [B, H, S, D]; splash takes per-batch [H, S, D]
    qt = (q * scale).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def make_splash_attention(window: int = 0, native_gqa: bool = False):
    """AttentionFn factory bound to a window size (strategy layer hook).

    ``native_gqa`` makes the model hand over UNREPEATED grouped KV
    (``supports_gqa``): n_rep x less KV activation memory, but measured
    ~20% slower than the repeat path at llama3 attention geometry on
    v5e (the per-group MQA calls batch worse than one wide MHA call) —
    enable when activation memory is the binding constraint.
    """
    fn = partial(splash_attention, window=window)
    fn.supports_gqa = bool(native_gqa)
    return fn
