"""Flash attention for TPU: jax's Pallas flash kernel in this repo's layout.

Reference analog: the reference glues flash-attn CUDA kernels into its
models (atorch/atorch/modules/transformer/layers.py FA wrappers; tfplus
ships its own fmha C++ op, tfplus/flash_attn/kernels/
flash_attention_fwd_kernel.cc:28). The TPU-native equivalent is a Pallas
kernel: ``flash_attention(q, k, v, causal=...)`` is a drop-in for
models.transformer.dense_attention ([B, S, H, D] layout) that always
dispatches to jax's tiled fwd + bwd kernel
(jax.experimental.pallas.ops.tpu.flash_attention). There is no silent
fallback: off-TPU the Pallas lowering raises. Tests on the CPU mesh that
need a model configured with a kernel attention enter
``reference_kernels()`` and get the dense einsum instead.
"""

from __future__ import annotations

import contextlib
import math

import jax

_reference = False


@contextlib.contextmanager
def reference_kernels():
    """Inside, ``flash_attention`` and ``splash_attention`` trace the
    dense einsum reference in place of their TPU kernels. For tests on
    the CPU mesh only: nothing in the program enters it, so a kernel
    config on a device without the kernel is an error, not dense."""
    global _reference
    prev, _reference = _reference, True
    try:
        yield
    finally:
        _reference = prev


def reference_enabled() -> bool:
    return _reference


def _block_for(seq: int) -> int:
    """Largest power-of-two block <= 1024 that divides ``seq``."""
    return math.gcd(seq, 1024)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """Training-path flash attention, dense_attention-compatible:
    jax's production Pallas kernel (tiled fwd AND bwd — the bwd is what
    keeps long-seq training memory flat)."""
    if _reference:
        from dlrover_tpu.models.transformer import dense_attention

        return dense_attention(q, k, v, causal=causal)
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    # [B, S, H, D] -> [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # 1024-sized q/k blocks measured 4.1x faster than the kernel's
    # defaults for fwd+bwd at seq 4096 / d 64 on v5e (14.8ms vs 60.8ms,
    # batch 4 x 12 heads); blocks must divide the sequence, so take
    # gcd(seq, 1024) — a power-of-two divisor, 1024 whenever seq allows
    bq = _block_for(q.shape[1])
    bk = _block_for(k.shape[1])
    blocks = fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_q_dkv=bq, block_k_dkv=bk,
        block_q_dq=bq, block_k_dq=bk, block_k_major_dq=bk,
    )
    out = fa.flash_attention(
        qt, kt, vt, causal=causal,
        sm_scale=1.0 / math.sqrt(q.shape[-1]),
        block_sizes=blocks,
    )
    return out.transpose(0, 2, 1, 3)
