"""The main path's kernels and step programs, compiled for a described
``v5e:2x2`` at real widths — no chip, no run.

Interpret mode and the CPU backend cannot show what the TPU compiler
refuses (block shapes off the (8, 128) tiling, too much VMEM, a program
that does not fit 16 GB, a kernel that cannot be partitioned). These
compiles can, at about two seconds a kernel and some more for a step
program. Nothing here executes; a pass is not a chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process at a time may load libtpu, and every xdist
worker imports every test file), and every compile runs in the test's
own process. Keep these tests in this one file.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, SingleDeviceSharding

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.parallel import strategy as strat_lib
from dlrover_tpu.parallel.compile_cache import executable_stats
from dlrover_tpu.trainer.train_step import compile_train

HBM_BYTES = 16 * 10**9  # v5e, utils/profiler.py PEAKS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _abstract_train_args(compiled_train, batch: int, seq: int):
    state = jax.eval_shape(compiled_train.init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh),
        state, compiled_train.state_shardings,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    tokens = jax.ShapeDtypeStruct(
        (1, batch, seq + 1), np.int32,
        sharding=compiled_train.batch_sharding)
    return state, {"tokens": tokens}


def _compile_train_step(cfg, strategy, devices, batch, seq):
    mesh = strategy.build_mesh(devices)
    ct = compile_train(
        strategy=strategy, mesh=mesh,
        loss_fn=tfm.make_loss_fn(cfg, strategy, mesh),
        init_params_fn=lambda rng: tfm.init_params(cfg, rng),
        logical_params=tfm.logical_axes(cfg),
        optimizer=optax.adamw(1e-4),
    )
    state, batch_abs = _abstract_train_args(ct, batch, seq)
    return ct, state, ct.step.lower(state, batch_abs).compile()


@pytest.mark.parametrize("kernel", ["flash", "splash", "splash_window"])
def test_attention_kernels_compile_fwd_bwd(one_chip, kernel):
    """gpt2-medium attention geometry (B2 S1024 H16 D64, bf16), forward
    and backward, as the train step calls them."""
    from dlrover_tpu.ops.flash_attention import flash_attention
    from dlrover_tpu.ops.splash_attention import splash_attention

    fn = {
        "flash": flash_attention,
        "splash": splash_attention,
        "splash_window": lambda q, k, v, causal: splash_attention(
            q, k, v, causal=causal, window=256),
    }[kernel]

    def loss(q, k, v):
        return fn(q, k, v, causal=True).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((2, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # fwd + bwd


def test_gpt2_medium_train_step_fits_one_chip(topo):
    """The whole step ``chip_smoke.py`` trains (full width and depth,
    batch 8 x 1024, AdamW in f32) holds a Pallas kernel and leaves room
    for the async snapshot's copy of the state."""
    cfg = dataclasses.replace(
        tfm.CONFIGS["gpt2-medium"], attention="splash", remat_scan=True,
        remat_policy="nothing", ce_chunks=16)
    _, state, compiled = _compile_train_step(
        cfg, strat_lib.dp(), topo.devices[:1], batch=8, seq=1024)
    assert "tpu_custom_call" in compiled.as_text()
    state_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(state))
    assert _device_bytes(compiled) + state_bytes < HBM_BYTES


def test_fsdp_step_shards_over_four_chips(topo):
    """gpt2-xl widths (d 1600, 25 heads) with the depth cut to 2 layers,
    FSDP on the 2x2 mesh: every chip holds a quarter of the state and the
    step gathers parameters over the interconnect."""
    cfg = dataclasses.replace(
        tfm.CONFIGS["gpt2-xl"], n_layers=2, attention="splash",
        remat_scan=True, remat_policy="nothing", ce_chunks=16)
    ct, state, compiled = _compile_train_step(
        cfg, strat_lib.fsdp(), topo.devices, batch=8, seq=1024)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    total = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for leaf in jax.tree_util.tree_leaves(state))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 0.3 * total  # ~1/4 + the replicated small leaves
    assert _device_bytes(compiled) < HBM_BYTES
    assert isinstance(ct.state_shardings.params["embed"], NamedSharding)


def test_serving_programs_compile(one_chip):
    """Prefill chunk and decode step of ``InferenceEngine`` at
    gpt2-medium widths (depth cut to 1), the step with the canonical
    numerics the engine compiles it under. The step's full-vocabulary
    sampling makes it the slow compile of this file (~25 s); the decode
    block and the verify program share its body and are compiled at
    full depth by the chip smoke."""
    from dlrover_tpu.models.decode import init_cache
    from dlrover_tpu.serving import engine as serving

    cfg = dataclasses.replace(tfm.CONFIGS["gpt2-medium"], n_layers=1)
    eng = serving.InferenceEngine(
        tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg, slots=8)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), jnp.asarray(a).dtype, sharding=one_chip),
            tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=1
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    # ISSUE 26: the chip's compiler takes the donation: K and V alias
    # input to output (with pos and last), one stack resident, not two
    stack = int(np.prod(eng._cache["k"].shape)) * 2
    assert step.memory_analysis().alias_size_in_bytes >= 2 * stack
    chunk = jax.ShapeDtypeStruct((1, eng.prefill_len), jnp.int32,
                                 sharding=one_chip)
    row = on_chip(init_cache(cfg, 1, eng.max_len))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    eng._prefill_chunk.lower(
        on_chip(eng.params), chunk, row, scalar
    ).compile()


def test_latent_expert_serving_programs_compile(one_chip, monkeypatch):
    """Decode step and prefill chunk of ``InferenceEngine`` for the
    latent-attention / routed-expert kinds at openPangu-Ultra-MoE's
    published widths: one dense and one expert layer, 16 of 256 experts
    held, an eighth of the vocabulary, weights resting in bfloat16. What
    the CPU cannot show: the grouped kernel inside the layer scan (the
    program is traced as on a TPU: ``held_expert_ffn`` asks the
    backend), the expert stacks read in place (no copy of a stack in the
    program), the latent cache donated."""
    from dlrover_tpu.models import latent
    from dlrover_tpu.serving import engine as serving

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = dataclasses.replace(
        tfm.CONFIGS["openpangu-ultra-moe-718b"], n_layers=2,
        first_k_dense=1, experts_held=16, vocab_size=19200)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        latent.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    eng = serving.InferenceEngine(params, cfg, slots=16, max_len=5120,
                                  prefill_len=512)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=one_chip), tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=1
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    stack = eng._cache["latent"]
    assert stack.shape == (2, 16, 5120, 576)
    assert executable_stats(step)["pallas_calls"] == 1
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= stack.size * 2      # donated whole
    weights = cfg.param_count * 2
    # beside the weights and the cache: no second copy of an expert stack
    # (1.5e9 here) among the temporaries
    assert m.temp_size_in_bytes < 1.0e9 < weights < _device_bytes(step)
    assert _device_bytes(step) < HBM_BYTES
    row = on_chip(jax.eval_shape(lambda: latent.init_cache(cfg, 1, 5120)))
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
        row, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    assert executable_stats(chunk)["pallas_calls"] == 1
    assert chunk.memory_analysis().temp_size_in_bytes < 1.0e9


def test_hybrid_cache_serving_programs_compile(one_chip):
    """Decode step and prefill chunk of ``InferenceEngine`` for a cache of
    rows AND state (``attn_kind='mixers'``, models/hybrid.py) at
    MiniCPM-SALA's published widths: one block-sparse and one lightning
    layer, 16 slots of 33792 positions, weights resting in bfloat16. What
    the CPU cannot show: the gather of 64 selected blocks a row and group
    straight from the stack (no copy of a layer's rows: the first layout
    of the stacks made the compiler re-lay 830 MB a call), rows, compressed
    keys and state all donated."""
    from dlrover_tpu.models import hybrid
    from dlrover_tpu.serving import engine as serving

    cfg = dataclasses.replace(
        tfm.CONFIGS["minicpm-sala"], n_layers=2,
        mixer_types=("sparse", "lightning"), dtype="bfloat16")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        hybrid.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    eng = serving.InferenceEngine(params, cfg, slots=16, max_len=33792,
                                  prefill_len=512)
    assert eng.cache_bytes_per_token == 1024 + 32
    assert eng.state_bytes_per_slot == 32 * 128 * 128 * 4

    def on_chip(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=one_chip), tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=1
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    rows = eng._cache["k"]
    assert rows.shape == (2, 16, 33792, 128)            # G heads side by side
    held = (2 * rows.size * 2 + eng._cache["kc"].size * 2
            + eng._cache["state"]["s"].size * 4)
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                # donated whole
    # beside the weights and the cache: nothing the size of a row stack
    # (0.277e9) among the temporaries but the projections' re-laid weights
    assert m.temp_size_in_bytes < 0.5e9
    assert _device_bytes(step) < HBM_BYTES
    row = on_chip(jax.eval_shape(lambda: hybrid.init_cache(cfg, 1, 33792)))
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
        row, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    assert chunk.memory_analysis().temp_size_in_bytes < 1.0e9


def test_state_space_serving_programs_compile(one_chip, monkeypatch):
    """Decode step and prefill chunk of ``InferenceEngine`` for a stack of
    single-sublayer layers (``transformer.SINGLE_MIXERS``, models/hybrid.py)
    at Nemotron-3-Super's published widths: one Mamba-2, one latent-expert
    (128 of 512 held) and one attention layer, 32 slots of 3072 positions,
    weights resting in bfloat16. What the CPU cannot show: the float32 state
    of 32 rows (134 MB a layer) and the window donated and updated in place,
    the grouped kernel (the program is traced as on a TPU) reading an
    expert's two latent-width matrices straight from the stacks, nothing
    the size of a layer's experts (1.4 GB) among the temporaries."""
    from dlrover_tpu.models import hybrid
    from dlrover_tpu.serving import engine as serving

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = dataclasses.replace(
        tfm.CONFIGS["nemotron-3-super-120b-a12b"], n_layers=3,
        mixer_types=tfm.single_mixers("ME*"), experts_held=128,
        vocab_size=32768, dtype="bfloat16")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        hybrid.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    eng = serving.InferenceEngine(params, cfg, slots=32, max_len=3072,
                                  prefill_len=512)
    assert eng.cache_bytes_per_token == 1024
    assert eng.state_bytes_per_slot == 128 * 64 * 128 * 4 + 3 * 10240 * 2

    def on_chip(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=one_chip), tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=1
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    state = eng._cache["state"]
    assert state["ssm"].shape == (1, 32, 128, 64, 128)
    assert executable_stats(step)["pallas_calls"] == 1
    held = (state["ssm"].size * 4 + state["conv"].size * 2
            + 2 * eng._cache["k"].size * 2)
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                # donated whole
    # beside the weights and the cache: no copy of the experts' stacks
    assert m.temp_size_in_bytes < 0.7e9
    assert _device_bytes(step) < HBM_BYTES
    row = on_chip(jax.eval_shape(lambda: hybrid.init_cache(cfg, 1, 3072)))
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
        row, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    assert executable_stats(chunk)["pallas_calls"] == 1
    assert chunk.memory_analysis().temp_size_in_bytes < 0.7e9


def test_windowed_ring_serving_programs_compile(one_chip, monkeypatch):
    """Decode block and prefill chunk of ``InferenceEngine`` for a stack of
    windowed and full layers (``layer_windows``, models/decode.py) at
    SmallThinker's published widths: one period (a full layer without
    rotary embedding, three windowed ones), 24 slots of 16384 positions,
    weights resting in bfloat16. What the CPU cannot show: full rows and
    rings (heads before positions) donated and updated in place with no
    copy of a stack into another layout (with positions first the decode
    call held 2.9 GB of temporaries for 8 layers: a copy of every stack),
    the grouped kernel's ReGLU form read straight from the stacks, and a
    chunk's attention over a row cut to what its last query reaches."""
    from dlrover_tpu.models import decode
    from dlrover_tpu.serving import engine as serving

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    base = tfm.CONFIGS["smallthinker-21b-a3b-instruct"]
    cfg = dataclasses.replace(
        base, n_layers=4, layer_windows=base.layer_windows[:4],
        layer_rope=base.layer_rope[:4], vocab_size=32768, dtype="bfloat16")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        tfm.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    eng = serving.InferenceEngine(params, cfg, slots=24, max_len=16384,
                                  prefill_len=512, decode_block=8)
    row_bytes = 2 * 4 * 128 * 2
    assert eng.cache_bytes_per_token == row_bytes            # one full layer
    assert eng.state_bytes_per_slot == 3 * 4096 * row_bytes  # three rings

    def on_chip(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=one_chip), tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=8
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    rings = eng._cache["state"]
    assert rings["k_win"].shape == (3, 24, 4, 4096, 128)
    assert eng._cache["k"].shape == (1, 24, 4, 16384, 128)
    assert executable_stats(step)["pallas_calls"] == 2      # one a run
    held = 2 * 2 * (rings["k_win"].size + eng._cache["k"].size)
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                # donated whole
    # beside the weights and the cache: no copy of a stack (0.8 and 0.6
    # GB each), nor of a layer's experts (0.75 GB)
    assert m.temp_size_in_bytes < 0.7e9
    assert _device_bytes(step) < HBM_BYTES
    row = on_chip(jax.eval_shape(lambda: decode.init_cache(cfg, 1, 16384)))
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
        row, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    assert executable_stats(chunk)["pallas_calls"] == 2
    assert chunk.memory_analysis().temp_size_in_bytes < 0.7e9


def test_post_norm_shared_expert_serving_programs_compile(one_chip,
                                                          monkeypatch):
    """Decode block and prefill chunk of ``InferenceEngine`` for K-EXAONE's
    held share at the cell's sizes (layers 0-4: the dense layer's tree
    beside the expert layers', 16 of 128 experts, an eighth of the
    vocabulary; 48 slots of 8192 positions, a ring of 128 under chunks of
    512). What the CPU cannot show: the grouped kernel once for each run
    of expert layers (three) and none for the dense layer's, the full
    layer's rows and the four rings donated and updated in place at 48
    unrolled row writes, and the chunk's wide form (640 keys a windowed
    layer) beside the full layer's reach within a chip's memory."""
    from dlrover_tpu.models import decode
    from dlrover_tpu.serving import engine as serving

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    base = tfm.CONFIGS["k-exaone-236b-a23b"]
    cfg = dataclasses.replace(
        base, n_layers=5, layer_windows=base.layer_windows[:5],
        layer_rope=base.layer_rope[:5], experts_held=16, vocab_size=19200,
        dtype="bfloat16")
    assert [r.key for r in tfm.stack_runs(cfg)] == [
        "dense_layers", "layers", "layers", "layers"]
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        tfm.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    eng = serving.InferenceEngine(params, cfg, slots=48, max_len=8192,
                                  prefill_len=512, decode_block=8)
    row_bytes = 2 * 8 * 128 * 2
    assert eng.cache_bytes_per_token == row_bytes            # one full layer
    assert eng.state_bytes_per_slot == 4 * 128 * row_bytes   # four rings

    def on_chip(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=one_chip), tree)

    step = eng._step_block.lower(
        *on_chip(eng._block_sample_args()), n_steps=8
    ).compile(compiler_options=serving._CANONICAL_NUMERICS)
    rings = eng._cache["state"]
    assert rings["k_win"].shape == (4, 48, 8, 128, 128)
    assert eng._cache["k"].shape == (1, 48, 8, 8192, 128)
    assert executable_stats(step)["pallas_calls"] == 3   # one an expert run
    held = 2 * 2 * (rings["k_win"].size + eng._cache["k"].size)
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                # donated whole
    # beside 7.42 GB of weights and 1.71 of cache: no copy of the full
    # layer's stacks (0.8 GB each) nor of a layer's experts (1.2 GB)
    assert m.temp_size_in_bytes < 0.9e9
    assert _device_bytes(step) < HBM_BYTES
    row = on_chip(jax.eval_shape(lambda: decode.init_cache(cfg, 1, 8192)))
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
        row, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    assert executable_stats(chunk)["pallas_calls"] == 3
    assert chunk.memory_analysis().temp_size_in_bytes < 0.9e9


# the plain per-head tree's two cells: the model's widths (a smaller
# vocabulary, few experts: neither is in attention's way), the engine's
# slots and row length, and the stacks they give
ATTENTION_CELLS = {
    "gpt2-medium": (dict(n_layers=2), 16, 1024, (2, 16, 1024, 1024)),
    "sdar-30b-a3b-chat": (dict(n_layers=2, n_routed_experts=8, moe_top_k=2,
                               vocab_size=8192, mask_token_id=8000),
                          16, 2560, (2, 16, 2560, 512)),
}


@pytest.mark.parametrize("cell", ATTENTION_CELLS)
def test_decode_block_reads_the_stacks_in_place(one_chip, monkeypatch, cell):
    """ISSUE 43: the decode program of the plain per-head tree, traced
    as on a TPU at gpt2-medium's heads (16 x 64: a decode block's steps)
    and at SDAR's (32 on 4 of 128: a block's denoising and storing
    passes), holds the cached attention kernel
    (``ops/cached_attention.py``) and no operation that copies or
    re-lays a whole stack: the stacks are donated, written in place and
    read in place, so beside them the program's temporaries stay far
    under ONE stack (with positions before heads and the einsum the
    compiler copied both stacks into the layout its products read at
    the start of every call and back at its end: 3.2 GB of temporaries
    at gpt2-medium's 24 layers)."""
    from dlrover_tpu.serving import engine as serving

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    change, slots, max_len, stack = ATTENTION_CELLS[cell]
    cfg = dataclasses.replace(tfm.CONFIGS[cell], **change)
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    eng = serving.InferenceEngine(params, cfg, slots=slots, max_len=max_len,
                                  prefill_len=64)
    assert eng._cache["k"].shape == eng._cache["v"].shape == stack

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), jnp.asarray(a).dtype, sharding=one_chip),
            tree)

    if cfg.generation == "block_diffusion":
        weights, cache, _, *rest = on_chip(eng._step_sample_args())
        block = jax.ShapeDtypeStruct((slots, cfg.block_length), jnp.int32,
                                     sharding=one_chip)
        lowered = eng._denoise_blocks.lower(
            weights, cache, block,
            jax.ShapeDtypeStruct(block.shape, jnp.bool_, sharding=one_chip),
            *rest, n_blocks=1)
    else:
        lowered = eng._step_block.lower(
            *on_chip(eng._block_sample_args()), n_steps=2)
    compiled = lowered.compile(compiler_options=serving._CANONICAL_NUMERICS)
    text = compiled.as_text()
    assert executable_stats(compiled)["pallas_calls"] >= 1
    assert "cached_decode_attention" in text
    whole = "bf16[" + ",".join(map(str, stack)) + "]"
    copies = [line.strip()[:160] for line in text.splitlines()
              if " copy(" in line and whole in line.split(" copy(")[0]]
    assert not copies, copies
    m = compiled.memory_analysis()
    stack_bytes = int(np.prod(stack)) * 2
    assert m.alias_size_in_bytes >= 2 * stack_bytes       # donated whole
    assert m.temp_size_in_bytes < stack_bytes


@pytest.mark.parametrize("call", ATTENTION_CELLS)
def test_cached_attention_kernel_compiles_at_the_cells_heads(one_chip, call):
    """The kernel alone under a traced layer index at each cell's decode
    shape and at a verify block's (five causal queries a row), bfloat16
    stacks of six layers: Mosaic takes the block shapes the rule gives,
    and the stacks are read in place (no temporary the size of a
    layer's rows)."""
    from dlrover_tpu.ops.cached_attention import cached_attention, walk

    cfg = tfm.CONFIGS[call]
    _, slots, max_len, (_, _, _, lanes) = ATTENTION_CELLS[call]
    n_rep = cfg.n_heads // cfg.n_kv_heads

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = on_chip((6, slots, max_len, lanes), jnp.bfloat16)
    for queries in (1, cfg.block_length or 5):
        compiled = jax.jit(
            lambda q, k, v, layer, limits: cached_attention(
                q, k, v, layer,
                walk(limits, k.shape, 2, cfg.n_kv_heads, n_rep), n_rep=n_rep)
        ).lower(on_chip((slots, queries, cfg.n_heads, cfg.head_dim),
                        jnp.bfloat16), stack, stack, on_chip((), jnp.int32),
                on_chip((slots, queries), jnp.int32)).compile()
        assert executable_stats(compiled)["pallas_calls"] == 1
        layer_bytes = slots * max_len * lanes * 2
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 4


# one call of ``held_expert_ffn`` as the four expert cells make it, decode
# step and prefill chunk: tokens, M, F, held of experts, a token's, first
EXPERT_CALLS = {
    "sdar.decode": (64, 2048, 768, 128, 128, 8, 0, "swiglu"),
    "sdar.chunk": (512, 2048, 768, 128, 128, 8, 0, "swiglu"),
    "nemotron.decode": (32, 1024, 2688, 128, 512, 22, 128, "relu2"),
    "nemotron.chunk": (512, 1024, 2688, 128, 512, 22, 128, "relu2"),
    "openpangu.decode": (16, 7680, 2048, 16, 256, 8, 16, "swiglu"),
    "openpangu.chunk": (512, 7680, 2048, 16, 256, 8, 16, "swiglu"),
    "smallthinker.decode": (24, 2560, 768, 64, 64, 6, 0, "reglu"),
    "smallthinker.chunk": (512, 2560, 768, 64, 64, 6, 0, "reglu"),
    "k-exaone.decode": (48, 6144, 2048, 16, 128, 8, 0, "swiglu"),
    "k-exaone.chunk": (512, 6144, 2048, 16, 128, 8, 0, "swiglu"),
}


@pytest.mark.parametrize("call", EXPERT_CALLS)
def test_held_expert_kernel_compiles_at_the_cells_widths(one_chip, call):
    """The grouped kernel (``ops/grouped_ffn.py``) under a traced layer
    index at each expert cell's decode and chunk shape, bfloat16 stacks
    of six layers. What interpret mode cannot show: Mosaic takes the
    block shapes the rule gives (openPangu's F in four blocks under the
    VMEM limit asked for), the stacks are read in place (no temporary
    the size of a layer's experts), and no ``while`` is left."""
    from dlrover_tpu.ops import moe

    T, M, F, held, n_experts, k, first, form = EXPERT_CALLS[call]
    rcfg = moe.RoutedConfig(n_experts=n_experts, top_k=k, first=first,
                            held=held, form=form)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    names = ("we_up", "we_down") + (("we_gate",) * (form != "relu2"))
    experts = {n: on_chip((6, held) + ((F, M) if n == "we_down" else (M, F)),
                          jnp.bfloat16) for n in names}
    compiled = jax.jit(
        lambda x, idx, gate, experts, layer: moe.held_expert_kernel(
            x, idx, gate, experts, layer, rcfg)
    ).lower(on_chip((T, M), jnp.bfloat16), on_chip((T, k), jnp.int32),
            on_chip((T, k), jnp.float32), experts, on_chip((), jnp.int32)
            ).compile()
    assert executable_stats(compiled)["pallas_calls"] == 1
    assert " while(" not in compiled.as_text()
    layer_bytes = len(names) * held * M * F * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 4
