"""A decode call's attention over the cached rows as ONE Pallas TPU kernel.

Reference analog: none (the reference serves through vLLM's paged
attention). The rows rest in the carried stacks ``[L, B, max_len,
H_kv * D]`` (models/decode.py: a key's heads side by side on the lanes,
so the default layout is row-major with full tiles and nothing pads a
head of 64 to 128 lanes). The kernel reads ``stack[layer, b]`` IN PLACE,
in blocks of ``tk`` keys chosen by block index maps from scalar-
prefetched operands, in the idiom of ``ops/grouped_ffn.py``: the grid
walks the (row, key block) pairs that hold a live key (:func:`walk`,
made once a call for all its layers) and is as long as those pairs, so a block past a row's key limit is neither fetched nor
multiplied and costs no step; a row's last live block is masked inside.

A row's ``S`` queries of ``H = G * n_rep`` heads are laid out as ONE
block-diagonal matrix ``[G * R, G * D]`` (``R`` rows a key/value group:
its ``n_rep * S`` queries, each with its head's ``D`` numbers on its
group's lanes and zeros elsewhere), so the per-head scores are one MXU
product against the block ``[tk, G * D]`` as it lies, and the values
product gives ``[G * R, G * D]`` of which each row keeps its group's
lanes. Softmax over the blocks is the running form: maxima, sums and
the accumulator in float32, the probabilities rounded once to the
products' dtype.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a block of keys is about this many bytes of one stack: long enough
# that a grid step's fixed cost hides under its read, short enough that
# what a row's last block holds past its limit stays small
BLOCK_BYTES = 512 << 10
# rows of the block-diagonal query matrix the kernel takes: a decode
# step's, a block-diffusion pass's, a verify block's. A prefill chunk's
# 64 or 512 queries a head pass it and keep the einsum: through the
# kernel gpt2-medium's chunk (1024 rows) took the same 2.06 ms of device
# time as through the einsum, 2.09 (PERF.md section 6, PR 43)
MAX_QUERY_ROWS = 256


def key_block(max_len: int, lanes: int, itemsize: int) -> int:
    """Keys a grid step reads of a row: the largest of 512, 256, 128
    that divides ``max_len`` and whose block is at most `BLOCK_BYTES`
    (128 where none is); 0 where 128 does not divide ``max_len``."""
    fits = [tk for tk in (512, 256, 128) if max_len % tk == 0]
    if not fits:
        return 0
    return next((tk for tk in fits
                 if tk * lanes * itemsize <= BLOCK_BYTES), 128)


def group_rows(n_queries: int, n_rep: int) -> int:
    """Rows of the query matrix a key/value group takes: its ``n_rep *
    n_queries`` queries, padded to the float32 sublane tile where there
    is more than one (the kernel cuts the accumulator by group)."""
    rows = n_rep * n_queries
    return rows if rows == 1 else -(-rows // 8) * 8


def takes(q_shape: tuple, stack_shape: tuple, n_rep: int,
          itemsize: int) -> bool:
    """Whether the kernel takes a call of these shapes (``q [B, S, H,
    D]`` over stacks ``[L, B, max_len, G * D]``): the keys divide into
    blocks, the lanes into whole tiles, and the query matrix is at most
    `MAX_QUERY_ROWS` rows."""
    _, S, H, _ = q_shape
    max_len, lanes = stack_shape[2:]
    return (len(stack_shape) == 4 and lanes % 128 == 0
            and key_block(max_len, lanes, itemsize) > 0
            and (H // n_rep) * group_rows(S, n_rep) <= MAX_QUERY_ROWS)


class Walk(NamedTuple):
    """What a call's rows ask of the kernel, the same for every layer of
    the call (made once, outside the layer loop): the grid's (row, key
    block) pairs, each row's live blocks and least and largest key
    limit, the limit of each row of the query matrix, and the keys the
    kernel fetches of each row."""
    row: jax.Array          # [B * n_blocks] the step's row
    block: jax.Array        # [B * n_blocks] the step's key block
    total: jax.Array        # [] steps that hold a live key
    blocks: jax.Array       # [B] live key blocks
    least: jax.Array        # [B] the row's least key limit
    most: jax.Array         # [B] its largest
    row_limits: jax.Array   # [B, G * R, 1]
    keys_read: jax.Array    # [B]


def walk(limits: jax.Array, stack_shape: tuple, itemsize: int,
         groups: int, n_rep: int) -> Walk:
    """The `Walk` of rows whose query ``i`` sees key ``k`` iff ``k <
    limits[b, i]`` (``[B, S]``, each at least 1: a causal query's
    position plus one, a block-diffusion query's block's end) over
    stacks of ``stack_shape``: a row is read in blocks of
    :func:`key_block` keys as far as its largest limit reaches, at
    least one block (an idle row's stale position reads like any
    other); the grid steps past ``total`` repeat the last."""
    B, S = limits.shape
    max_len, lanes = stack_shape[2:]
    tk = key_block(max_len, lanes, itemsize)
    limits = limits.astype(jnp.int32)
    most = jnp.max(limits, axis=1)
    blocks = jnp.clip(-(-jnp.minimum(most, max_len) // tk), 1,
                      max_len // tk).astype(jnp.int32)
    ends = jnp.cumsum(blocks)
    total = ends[-1]
    v = jnp.minimum(jnp.arange(B * (max_len // tk), dtype=jnp.int32),
                    total - 1)
    row = jnp.minimum(jnp.searchsorted(ends, v, side="right",
                                       method="compare_all"),
                      B - 1).astype(jnp.int32)
    block = (v - (ends[row] - blocks[row])).astype(jnp.int32)
    row_limits = _by_group(
        jnp.broadcast_to(limits[:, None, None, :, None],
                         (B, groups, n_rep, S, 1)),
        group_rows(S, n_rep)).reshape(B, -1, 1)
    return Walk(row, block, total.astype(jnp.int32), blocks,
                jnp.min(limits, axis=1), most, row_limits, blocks * tk)


def _by_group(x: jax.Array, R: int) -> jax.Array:
    """``[B, G, n_rep, S, n]`` as the query matrix's rows ``[B, G, R,
    n]``: a group's ``n_rep * S`` queries, padded to ``R``."""
    B, G, n_rep, S, n = x.shape
    return jnp.pad(x.reshape(B, G, n_rep * S, n),
                   ((0, 0), (0, 0), (0, R - n_rep * S), (0, 0)))


def _kernel(row_ref, block_ref, blocks_ref, least_ref, most_ref, layer_ref,
            limit_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, groups: int, tk: int):
    del layer_ref
    step = pl.program_id(0)
    b, j = row_ref[step], block_ref[step]
    M, lanes = acc_ref.shape
    R, D = M // groups, lanes // groups

    @pl.when(j == 0)
    def _first():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(masked: bool):
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [M, tk]
        v = v_ref[...]
        if masked:
            key = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(key < limit_ref[...], s, -1e30)
            # a key past the limit may hold anything: it enters no sum
            row = j * tk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < most_ref[b], v, jnp.zeros_like(v))
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m_old - m_new)
        l_ref[...] = fade * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = fade * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # every query of the row sees every key of the block
    whole = (j + 1) * tk <= least_ref[b]

    @pl.when(whole)
    def _whole():
        block(masked=False)

    @pl.when(jnp.logical_not(whole))
    def _edge():
        block(masked=True)

    @pl.when(j == blocks_ref[b] - 1)
    def _last():
        # row (g, r) keeps the lanes of its group g: folded, row r
        # holds every group's head (g, r) on that group's lanes
        if R == 1:
            out = acc_ref[...] / l_ref[...]
            mine = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) == (
                jax.lax.broadcasted_iota(jnp.int32, out.shape, 1) // D)
            fold = jnp.sum(jnp.where(mine, out, 0.0), axis=0, keepdims=True)
        else:
            group = jax.lax.broadcasted_iota(jnp.int32, (R, lanes), 1) // D
            fold = jnp.zeros((R, lanes), jnp.float32)
            for g in range(groups):
                at = pl.ds(g * R, R)
                fold += jnp.where(group == g, acc_ref[at, :] / l_ref[at, :],
                                  0.0)
        o_ref[...] = fold.astype(o_ref.dtype)


def cached_attention(q: jax.Array, k_stack: jax.Array, v_stack: jax.Array,
                     layer, rows: Walk, *, n_rep: int,
                     interpret: bool = False) -> jax.Array:
    """``q [B, S, H, D]`` over ``stack[layer]`` of ``k_stack``,
    ``v_stack`` ``[L, B, max_len, G * D]`` (``H = G * n_rep``), each row
    read in place as far as ``rows`` (:func:`walk`, of these shapes)
    says. Returns ``o [B, S, H, D]`` in the stacks' dtype. The caller
    has checked :func:`takes`."""
    B, S, H, D = q.shape
    max_len, lanes = k_stack.shape[2:]
    G, dt = H // n_rep, k_stack.dtype
    tk = key_block(max_len, lanes, jnp.dtype(dt).itemsize)
    R = group_rows(S, n_rep)
    M = G * R

    # the block-diagonal query matrix: row (g, rep, i) holds head (g,
    # rep) of query i on group g's lanes
    qg = _by_group(jnp.moveaxis(
        q.reshape(B, S, G, n_rep, D).astype(dt), 1, 3), R)
    qbd = jnp.einsum("bgrd,gh->bgrhd", qg, jnp.eye(G, dtype=dt)
                     ).reshape(B, M, lanes)

    def rows_of(v, row, *_):
        return row[v], 0, 0

    def keys_of(v, row, block, blocks, least, most, layer):
        return layer[0], row[v], block[v], 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), groups=G,
                          tk=tk),
        out_shape=jax.ShapeDtypeStruct((B, R, lanes), dt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(rows.total,),
            in_specs=[pl.BlockSpec((None, M, 1), rows_of),
                      pl.BlockSpec((None, M, lanes), rows_of),
                      pl.BlockSpec((None, None, tk, lanes), keys_of),
                      pl.BlockSpec((None, None, tk, lanes), keys_of)],
            out_specs=pl.BlockSpec((None, R, lanes), rows_of),
            scratch_shapes=[pltpu.VMEM((M, 1), jnp.float32),
                            pltpu.VMEM((M, 1), jnp.float32),
                            pltpu.VMEM((M, lanes), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="cached_decode_attention",
        interpret=interpret,
    )(rows.row, rows.block, rows.blocks, rows.least, rows.most,
      jnp.asarray(layer, jnp.int32).reshape(1),
      rows.row_limits, qbd, k_stack, v_stack)
    # row (rep, i) of the fold holds head (g, rep) on group g's lanes
    o = out[:, :n_rep * S].reshape(B, n_rep, S, G, D)
    return jnp.transpose(o, (0, 2, 3, 1, 4)).reshape(B, S, H, D)
