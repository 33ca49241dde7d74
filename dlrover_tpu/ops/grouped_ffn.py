"""The held experts' grouped feed-forward as ONE Pallas TPU kernel.

Reference analog: none (atorch's MoE runs one GEMM per expert through
its all_to_all dispatch). The assignments that landed on held experts
arrive sorted by expert; the kernel's grid walks the (row tile, expert)
pairs that hold a row, in the idiom of
``jax.experimental.pallas.ops.tpu.megablox.gmm``: a tile of ``tm``
sorted rows is visited once for every expert that owns some of it.
Unlike three ``gmm`` calls a visit fuses the expert's two or three
products, so its weights are read once; and the tokens ``x`` and the
sum ``y`` stay resident in VMEM: a visit picks its rows out of ``x`` by
a one-hot product and adds its result into ``y`` through the transposed
one-hot, so no ``[T * k, M]`` array of sorted rows exists anywhere.

The weight blocks are chosen by the block index maps from scalar-
prefetched operands (the visit's expert, the layer), so
``stack[layer, e]`` is read in place from the ``[L, held, M, F]``
stacks and the pipeline has expert ``e + 1``'s block in flight while
expert ``e``'s rows are multiplied. The grid is as long as the visits
that hold a row (a scalar operand, as ``gmm``'s is): an expert that got
no token reads no weight and costs no step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the kernel asks of a core's VMEM (a v5e's is 128 MiB). ``x``, ``y``
# and the row tile's buffers come first; the expert's weight blocks,
# double-buffered, get the rest, and where an expert's matrices pass it
# the grid takes F in blocks.
VMEM_BYTES = 100 << 20


def f_block(T: int, M: int, F: int, tm: int, n_stacks: int,
            itemsize: int) -> int:
    """Columns of F a grid step takes: all of F where the expert's
    ``n_stacks`` matrices fit twice over beside what is resident, else
    the largest divisor of F that is a multiple of 128 lanes and does."""
    # x and y whole; the tile's accumulator, last product, picked rows
    # and one-hot; per block, the expert's matrices twice and three
    # float32 [tm, bf] temporaries
    resident = T * M * (itemsize + 4) + tm * (M * (8 + itemsize)
                                              + T * itemsize)

    def fits(bf):
        return (resident + 2 * n_stacks * M * bf * itemsize
                + 3 * tm * bf * 4 <= VMEM_BYTES)

    if fits(F) or F % 128:
        return F
    blocks = [bf for bf in range(F - 128, 0, -128) if F % bf == 0]
    return next((bf for bf in blocks if fits(bf)), blocks[-1])


def visits(loads: jax.Array, n_tiles: int, tm: int):
    """The grid's (row tile, expert) pairs for sorted rows whose expert
    ``e`` owns rows ``[ends[e] - loads[e], ends[e])``: for each of at
    most ``held + n_tiles`` steps the expert, the tile, the expert's
    first row and the row behind its last; and how many steps hold a
    row. The entries past those repeat the last."""
    held = loads.shape[0]
    ends = jnp.cumsum(loads)
    starts = ends - loads
    per = jnp.where(loads > 0, -(-ends // tm) - starts // tm, 0)
    visit_ends = jnp.cumsum(per)
    total = visit_ends[-1]
    v = jnp.minimum(jnp.arange(held + n_tiles, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    e = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right",
                                     method="compare_all"),
                    held - 1).astype(jnp.int32)
    tile = starts[e] // tm + (v - (visit_ends[e] - per[e]))
    tile = jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32)
    return (e, tile, starts[e].astype(jnp.int32), ends[e].astype(jnp.int32),
            total.astype(jnp.int32))


def _kernel(e_ref, tile_ref, lo_ref, hi_ref, total_ref, layer_ref,
            tok_ref, g_ref, x_ref, *refs, form: str, n_f: int, tm: int):
    del e_ref, layer_ref
    *w_refs, y_ref, acc_ref, rows_ref, pick_ref = refs
    v, j = pl.program_id(0), pl.program_id(1)
    T, dt = x_ref.shape[0], rows_ref.dtype

    @pl.when((v == 0) & (j == 0))
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(v < total_ref[0])     # false only where nothing landed here
    def _visit():
        @pl.when(j == 0)
        def _pick():
            row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, T), 0)
            mine = (row >= lo_ref[v]) & (row < hi_ref[v])
            token = jax.lax.broadcasted_iota(jnp.int32, (tm, T), 1)
            pick = ((tok_ref[...] == token) & mine).astype(dt)
            pick_ref[...] = pick
            rows_ref[...] = jnp.dot(
                pick, x_ref[...].astype(dt),
                preferred_element_type=jnp.float32).astype(dt)

        def dot(a, w_ref):
            return jnp.dot(a.astype(dt), w_ref[...].astype(dt),
                           preferred_element_type=jnp.float32)

        rows = rows_ref[...]
        if form == "relu2":
            up_ref, down_ref = w_refs
            h = jnp.square(jnp.maximum(dot(rows, up_ref), 0.0))
        else:
            gate_ref, up_ref, down_ref = w_refs
            act = jax.nn.relu if form == "reglu" else jax.nn.silu
            h = act(dot(rows, gate_ref)) * dot(rows, up_ref)
        part = dot(h, down_ref)
        if n_f > 1:
            @pl.when(j == 0)
            def _first():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _rest():
                acc_ref[...] += part

        @pl.when(j == n_f - 1)
        def _add():
            out = acc_ref[...] if n_f > 1 else part
            out = (out * g_ref[...]).astype(dt)
            y_ref[...] += jax.lax.dot_general(
                pick_ref[...], out, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def grouped_ffn(x: jax.Array, tokens: jax.Array, gates: jax.Array,
                stacks: tuple, layer, loads: jax.Array, *, form: str,
                tm: int, interpret: bool = False) -> jax.Array:
    """``x [T, M]``; ``tokens``, ``gates`` ``[n]`` the assignments sorted
    by held expert (``n`` a multiple of ``tm``; expert ``e`` owns
    ``loads[e]`` of them behind the experts before it; what lies behind
    the last expert's is never read); ``stacks`` the expert's matrices
    ``[L, held, M, F]`` ... ``[L, held, F, M]`` in the order of its
    products (``form`` "swiglu" / "reglu": gate, up, down; "relu2": up, down).
    Returns ``y [T, M]`` float32: the sum over assignments ``r`` of
    expert ``e`` of ``gates[r] * expert_e(x[tokens[r]])``, each term
    float32 until it is rounded once to the products' dtype, the sum in
    float32."""
    (T, M), n = x.shape, tokens.shape[0]
    F = stacks[0].shape[-1]
    dt = jnp.result_type(x.dtype, stacks[0].dtype)
    bf = f_block(T, M, F, tm, len(stacks),
                 jnp.dtype(stacks[0].dtype).itemsize)
    n_f = F // bf
    e, tile, lo, hi, total = visits(loads, n // tm, tm)

    def rows(v, j, e, tile, *_):
        return tile[v], 0

    def whole(v, j, *_):
        return 0, 0

    def up(v, j, e, tile, lo, hi, total, layer):
        return layer[0], e[v], 0, j

    def down(v, j, e, tile, lo, hi, total, layer):
        return layer[0], e[v], j, 0

    once = pl.Buffered(1)     # one block for the whole grid: one buffer
    w_specs = [pl.BlockSpec((None, None, M, bf), up)] * (len(stacks) - 1)
    w_specs.append(pl.BlockSpec((None, None, bf, M), down))
    return pl.pallas_call(
        functools.partial(_kernel, form=form, n_f=n_f, tm=tm),
        out_shape=jax.ShapeDtypeStruct((T, M), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(jnp.maximum(total, 1), n_f),
            in_specs=[pl.BlockSpec((tm, 1), rows),
                      pl.BlockSpec((tm, 1), rows),
                      pl.BlockSpec((T, M), whole, pipeline_mode=once),
                      *w_specs],
            out_specs=pl.BlockSpec((T, M), whole),
            scratch_shapes=[pltpu.VMEM((tm, M), jnp.float32),
                            pltpu.VMEM((tm, M), dt),
                            pltpu.VMEM((tm, T), dt)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        name="held_expert_grouped_ffn",
        interpret=interpret,
    )(e, tile, lo, hi, total.reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), tokens.reshape(n, 1),
      gates.astype(jnp.float32).reshape(n, 1), x, *stacks)
