"""Compressed collectives: int8-quantized gradient reduction.

Reference analog: ATorch's CUDA quant-reduce kernels for communication
compression (atorch/atorch/ops/csrc/quantization/quant_reduce.cu) — the
gradient allreduce ships int8 payloads instead of f32/bf16. On TPU the
collectives are XLA's; compression is expressed in-graph.

Two transports:

- ``quantized_ring_mean`` (the default for a single axis): a ring
  reduce-scatter with per-hop requantization followed by an int8
  all-gather. Per-device wire bytes ~= 2x payload in int8 ~= 1/4 of the
  f32 ring allreduce it replaces, independent of axis size N — the shape
  that actually wins on a DCN-spanning data axis.
- ``quantized_gather_mean``: all-gather of everyone's int8 payload,
  O(N) bytes per device. Lower quantization error (single quantization,
  exact per-participant scales) but only cheaper than f32 allreduce for
  small N; used for multi-axis reductions where a single ring does not
  apply.

Both must run inside ``shard_map`` (they take mesh axis names).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def shard_map_nocheck(f: Callable, *, mesh, in_specs, out_specs) -> Callable:
    """``shard_map`` with replication/varying-axis checking disabled."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantized_gather_mean(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Mean across ``axes`` via int8 all-gather (O(N) per-device bytes)."""
    if not axes:
        return x
    axes = tuple(axes)
    q, scale = _quantize(x)
    qg = lax.all_gather(q, axes)                 # [N, ...]
    sg = lax.all_gather(scale, axes)             # [N]
    deq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * x.ndim)
    return deq.mean(0).astype(x.dtype)


def quantized_ring_mean(x: jax.Array, axis: str, n: int) -> jax.Array:
    """Mean across mesh ``axis`` (size ``n``) with int8 ring transport.

    Ring reduce-scatter: n-1 hops, each forwarding a requantized partial
    sum of one 1/n chunk; then an int8 all-gather of the reduced chunks.
    Per-device bytes ~= 2 * |x| in int8, independent of n. Requantizing
    at every hop accumulates error O(n * max|partial|/254) — still far
    below gradient noise for n in the tens.
    """
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    flat = x.reshape(-1).astype(jnp.float32)
    size = flat.shape[0]
    chunk = -(-size // n)  # ceil
    flat = jnp.pad(flat, (0, chunk * n - size))
    parts = flat.reshape(n, chunk)

    fwd = [(i, (i + 1) % n) for i in range(n)]

    # step k: device idx holds the running sum of chunk (idx - k) mod n,
    # forwards it, and absorbs the incoming sum of chunk (idx - k - 1)
    acc = lax.dynamic_index_in_dim(parts, idx % n, 0, keepdims=False)
    for k in range(n - 1):
        q, scale = _quantize(acc)
        q = lax.ppermute(q, axis, fwd)
        scale = lax.ppermute(scale, axis, fwd)
        incoming = q.astype(jnp.float32) * scale
        local = lax.dynamic_index_in_dim(
            parts, (idx - k - 1) % n, 0, keepdims=False
        )
        acc = incoming + local
    # device idx now owns the full sum of chunk (idx + 1) mod n
    q, scale = _quantize(acc)
    qg = lax.all_gather(q, axis)                 # [n, chunk] by device
    sg = lax.all_gather(scale, axis)             # [n]
    deq = qg.astype(jnp.float32) * sg[:, None]
    # device i's slot holds chunk (i + 1) mod n -> roll into chunk order
    ordered = jnp.roll(deq, 1, axis=0)
    out = ordered.reshape(-1)[:size] / n
    return out.reshape(x.shape).astype(x.dtype)


def quantized_tree_mean(
    tree: Any, axes: Sequence[str], axis_sizes: dict[str, int] | None = None
) -> Any:
    """Quantized mean over every leaf of a gradient pytree.

    Single axis -> ring transport (O(1) per-device bytes); multiple axes
    -> gather transport. ``axis_sizes`` (mesh.shape) is required for the
    ring path.
    """
    axes = tuple(axes)
    if len(axes) == 1 and axis_sizes is not None:
        n = int(axis_sizes[axes[0]])
        return jax.tree.map(
            lambda g: quantized_ring_mean(g, axes[0], n), tree
        )
    return jax.tree.map(lambda g: quantized_gather_mean(g, axes), tree)


def seq_parallel_spec(mesh, axis_name: str,
                      batch_axes: tuple[str, ...] = ("data", "fsdp"),
                      heads_axis: str = "tensor"):
    """The [B, S, H, D] PartitionSpec shared by the sequence-parallel
    attention wrappers (ring + Ulysses), or None when the mesh has no
    usable sequence axis (callers degrade to dense attention)."""
    from jax.sharding import PartitionSpec

    if axis_name not in mesh.axis_names or mesh.shape[axis_name] <= 1:
        return None
    batch = tuple(a for a in batch_axes if a in mesh.axis_names
                  and mesh.shape[a] > 1)
    b_spec = batch if len(batch) > 1 else (batch[0] if batch else None)
    h_spec = (
        heads_axis
        if heads_axis in mesh.axis_names and mesh.shape[heads_axis] > 1
        else None
    )
    return PartitionSpec(b_spec, axis_name, h_spec, None)
