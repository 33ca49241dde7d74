"""Mixture-of-Experts: top-k gating + expert-parallel dispatch.

Reference analog: atorch/atorch/modules/moe/ (moe_layer.py all_to_all
dispatch, topk_gating.py, switch_gating.py, ddp.py expert-aware grad
groups). TPU-native design: experts carry an "expert" logical axis that
the strategy maps onto the expert mesh axis; dispatch/combine are einsums
against a capacity-limited one-hot dispatch tensor, and XLA lowers the
resharding between token-sharded and expert-sharded layouts to all_to_all
collectives — no imperative dispatch code, and expert-parallel gradients
need no special DDP handling (they're just sharded arrays).

Gating follows the Switch/GShard recipe: softmax router, top-k experts
per token, per-expert capacity ``ceil(T/E * capacity_factor)`` with
overflow tokens dropped (their residual path passes through), and the
load-balancing auxiliary loss ``E * sum_e f_e * p_e``.

Which routing each family uses:

- ``moe_ffn`` (the ``tiny-moe`` preset, training and serving): softmax
  router, ReLU experts of two matrices, ``[T, E, C]`` one-hot dispatch,
  tokens past an expert's capacity dropped.
- ``sigmoid_topk_route`` + ``held_expert_ffn`` (the DeepSeek-V3 /
  openPangu-Ultra-MoE family, ``models/latent.py``, serving): sigmoid
  scores over ALL routed experts in float32, the ``top_k`` largest,
  gates normalised over the chosen and scaled; SwiGLU experts; NO
  capacity, so no token is dropped. The layer is told which experts it
  holds (``first``, ``held``: one chip's share of an expert-parallel
  deployment), routes over all of them and adds only its own experts'
  part; what the absent experts would add is left out, and no code
  stands in for the exchange. Assignments are sorted by expert and run
  as a grouped product over row tiles: the work follows the
  assignments that landed here, not ``T x held``, and an expert that
  got no token reads no weight. On a TPU the grouped product is one
  Pallas kernel (``ops/grouped_ffn.py``), elsewhere a ``while`` whose
  trip count is data; either way the layer is for the forward pass
  (scoring, serving), not for ``grad``.
- ``softmax_topk_route`` + ``held_expert_ffn`` (the Qwen3-MoE / SDAR
  family, ``ffn_kind='softmax_experts'`` of ``models/transformer.py``'s
  block, serving): softmax over ALL routed experts in float32, the
  ``top_k`` largest, renormalised over the chosen; the same grouped
  product, here with every expert held (``held == n_experts``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25


def init_moe_params(key: jax.Array, d_model: int, d_ff: int,
                    cfg: MoeConfig) -> dict:
    import math

    k_r, k_in, k_out = jax.random.split(key, 3)
    return {
        "w_router": jax.random.normal(
            k_r, (d_model, cfg.n_experts), jnp.float32
        ) / math.sqrt(d_model),
        "w_in": jax.random.normal(
            k_in, (cfg.n_experts, d_model, d_ff), jnp.float32
        ) / math.sqrt(d_model),
        "w_out": jax.random.normal(
            k_out, (cfg.n_experts, d_ff, d_model), jnp.float32
        ) / math.sqrt(d_ff),
    }


def moe_logical_axes(cfg: MoeConfig | None = None) -> dict:
    """Logical axes: experts shard over the "expert" mesh axis."""
    return {
        "w_router": ("embed", None),
        "w_in": ("expert", "embed", "mlp"),
        "w_out": ("expert", "mlp", "embed"),
    }


def _dispatch_tensors(gates: jax.Array, cfg: MoeConfig, capacity: int
                      ) -> tuple[jax.Array, jax.Array]:
    """(combine [T,E,C], dispatch mask [T,E,C]) for top-k routed tokens.

    GShard-style position assignment: tokens claim expert slots in order;
    tokens past an expert's capacity are dropped for that expert.
    """
    T, E = gates.shape
    combine = jnp.zeros((T, E, capacity), gates.dtype)
    remaining = gates
    # slots already used per expert by earlier k-iterations
    used = jnp.zeros((E,), jnp.int32)
    for _ in range(cfg.top_k):
        idx = jnp.argmax(remaining, axis=-1)                 # [T]
        onehot = jax.nn.one_hot(idx, E, dtype=gates.dtype)   # [T, E]
        gate_k = (remaining * onehot).sum(-1)                # [T]
        # position of each token within its chosen expert's buffer —
        # cumsum MUST run in int32: a bf16 cumsum cannot represent
        # integers past 256, so long sequences would collide tokens into
        # the same capacity slot (blended expert inputs)
        onehot_i = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        # a zero-gate token (e.g. masked out) claims no slot at all
        routed = (gate_k > 0)
        onehot_i = onehot_i * routed[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(onehot_i, axis=0) - onehot_i
               ) + used[None, :]                             # [T, E] i32
        pos_tok = (pos * onehot_i).sum(-1)                   # [T] i32
        keep = routed & (pos_tok < capacity)
        slot = jax.nn.one_hot(
            jnp.where(keep, pos_tok, capacity), capacity + 1,
            dtype=gates.dtype,
        )[:, :capacity]                                      # [T, C]
        combine = combine + (
            gate_k[:, None, None] * onehot[:, :, None] * slot[:, None, :]
        )
        used = used + (onehot_i * keep[:, None].astype(jnp.int32)).sum(0)
        remaining = remaining * (1.0 - onehot)
    dispatch = (combine > 0).astype(gates.dtype)
    return combine, dispatch


def moe_ffn(params: dict, x: jax.Array, cfg: MoeConfig,
            constrain=None, token_mask: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array]:
    """MoE feed-forward. x: [B, S, M] -> ([B, S, M], aux_loss scalar).

    ``constrain`` (strategy layer) pins the expert-sharded intermediates
    so XLA keeps expert compute on the expert mesh axis. ``token_mask``
    [B, S] excludes padding from routing, capacity, and the aux loss —
    pad tokens would otherwise evict real tokens from expert buffers.
    """
    import math

    B, S, M = x.shape
    T = B * S
    E = cfg.n_experts
    pin = constrain or (lambda v, a: v)
    xt = x.reshape(T, M)

    logits = (xt.astype(jnp.float32) @ params["w_router"]).astype(
        jnp.float32
    )
    gates = jax.nn.softmax(logits, axis=-1)                   # [T, E]
    if token_mask is not None:
        mask_t = token_mask.reshape(T).astype(jnp.float32)
        gates = gates * mask_t[:, None]
        n_real = jnp.maximum(mask_t.sum(), 1.0)
    else:
        mask_t = None
        n_real = float(T)

    # load-balancing aux loss over REAL tokens: fraction routed to e
    # (top-1) times mean router prob for e, scaled by E (Switch eq. 4)
    top1 = jax.nn.one_hot(jnp.argmax(gates, -1), E, dtype=jnp.float32)
    if mask_t is not None:
        top1 = top1 * mask_t[:, None]
    aux = E * jnp.sum(
        (top1.sum(0) / n_real) * (gates.sum(0) / n_real)
    )

    capacity = max(
        cfg.top_k, math.ceil(T / E * cfg.capacity_factor)
    )
    combine, dispatch = _dispatch_tensors(
        gates.astype(x.dtype), cfg, capacity
    )

    # [T,E,C] x [T,M] -> [E,C,M]: becomes an all_to_all when tokens are
    # batch-sharded and experts expert-sharded
    x_e = jnp.einsum("tec,tm->ecm", dispatch, xt)
    x_e = pin(x_e, ("expert", None, "embed"))
    h = jax.nn.relu(jnp.einsum("ecm,emf->ecf", x_e, params["w_in"].astype(
        x.dtype
    )))
    h = pin(h, ("expert", None, "mlp"))
    y_e = jnp.einsum("ecf,efm->ecm", h, params["w_out"].astype(x.dtype))
    y = jnp.einsum("tec,ecm->tm", combine, y_e)
    return y.reshape(B, S, M), aux.astype(jnp.float32)


# ------------------------------------------------- held experts, no drops


@dataclasses.dataclass(frozen=True)
class RoutedConfig:
    """A routed expert layer without capacity (sigmoid or softmax
    scores) and the share of it held here."""
    n_experts: int            # the router's width: every routed expert
    top_k: int
    scaling: float = 1.0      # routed_scaling_factor
    norm_topk: bool = True    # gates normalised over the chosen
    first: int = 0            # index of the first expert held here
    held: int = 0             # how many are held (0: all)
    # an expert's form: "swiglu" (three stacks we_gate, we_up, we_down),
    # "reglu" (the same three: Down(relu(Gate h) * Up h)) or "relu2" (two,
    # we_up and we_down: Down(relu(Up h)^2))
    form: str = "swiglu"

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts


def swiglu(h: jax.Array, w_gate, w_up, w_down,
           act=jax.nn.silu) -> jax.Array:
    """``Down(silu(Gate h) * Up h)`` on ``h [..., M]``; ``act`` in the
    place of ``silu`` is the gated form's other members (``jax.nn.relu``:
    ReGLU)."""
    gate = act(jnp.einsum("...m,mf->...f", h, w_gate))
    return jnp.einsum("...f,fm->...m",
                      gate * jnp.einsum("...m,mf->...f", h, w_up), w_down)


def relu2(h: jax.Array, w_up, w_down) -> jax.Array:
    """``Down(relu(Up h)^2)`` on ``h [..., M]``: the feed-forward kind with
    no gate."""
    return jnp.einsum("...f,fm->...m", jnp.square(jax.nn.relu(
        jnp.einsum("...m,mf->...f", h, w_up))), w_down)


def sigmoid_topk_route(h: jax.Array, w_router: jax.Array,
                       cfg: RoutedConfig, bias: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """``h [T, M]`` -> (expert ids ``[T, k]`` int32, gates ``[T, k]``
    float32) over ALL ``n_experts``. Scores are float32 products at
    ``HIGHEST``: the k-th and (k+1)-th score of a token can lie a
    rounding apart, and a flipped choice is a different function.
    ``bias [n_experts]`` (a score-correction bias; None: the function
    without one) enters the CHOICE alone: the k largest of ``score +
    bias``, their gates from the scores."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "tm,me->te", h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        top, idx = jax.lax.top_k(scores, cfg.top_k)
    else:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), top * cfg.scaling


def softmax_topk_route(h: jax.Array, w_router: jax.Array,
                       cfg: RoutedConfig) -> tuple[jax.Array, jax.Array]:
    """``h [T, M]`` -> (expert ids ``[T, k]`` int32, gates ``[T, k]``
    float32): ``softmax(h W_r)`` over ALL ``n_experts``, the ``top_k``
    largest, renormalised over the chosen (``norm_topk``). Float32
    products at ``HIGHEST``, as :func:`sigmoid_topk_route` and for its
    reason."""
    probs = jax.nn.softmax(jnp.einsum(
        "tm,me->te", h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), top * cfg.scaling


def sigmoid_expert_half(h: jax.Array, w: dict, experts: dict, layer,
                        cfg: RoutedConfig, dt) -> tuple[jax.Array, jax.Array]:
    """A 'sigmoid_experts' layer's feed-forward on its input ``h [B, S,
    E]`` (normed or not: the block's norm kind says), the ONE definition
    every block of that kind calls (``models/transformer.py``'s,
    ``models/latent.py``'s): the held experts' part of the sigmoid-routed
    sum (:func:`sigmoid_topk_route`, the choice by score + ``w['b_router']``
    where the layer has one; :func:`held_expert_ffn` on ``experts`` at
    ``layer``) beside the shared expert ``ws_*``, which every chip of a
    deployment computes alike. Returns ``(ff [B, S, E] in dt, loads
    [held])``."""
    ht = h.reshape(-1, h.shape[-1])
    with jax.named_scope("moe_router"):
        idx, gate = sigmoid_topk_route(ht, w["w_router"], cfg,
                                       bias=w.get("b_router"))
    with jax.named_scope("moe_experts"):
        routed, loads = held_expert_ffn(ht, idx, gate, experts, layer, cfg)
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, w["ws_gate"].astype(dt), w["ws_up"].astype(dt),
                        w["ws_down"].astype(dt))
    return shared + routed.reshape(h.shape).astype(dt), loads


def held_counters(n_layers: int, n_held: int) -> dict:
    """What a cache tree keeps of its expert layers, which every cached
    call adds to: ``loads [expert layers, held]`` (assignments each held
    expert took) and the scalars a span reports by these names
    (``decode.cache_counter_fields``): ``expert_tokens`` (the sum of
    ``loads``), ``expert_load_max`` (its largest cell),
    ``expert_load_max_over_mean`` (that cell against the mean cell; 1.0
    is even) and ``experts_hit`` (held experts that took at least one
    assignment, summed over layers and calls). A buffer of its own for
    each: the tree is donated leaf by leaf."""
    return {
        "loads": jnp.zeros((n_layers, n_held), jnp.int32),
        **{name: jnp.zeros((), jnp.int32) for name in (
            "expert_tokens", "expert_load_max", "experts_hit")},
        "expert_load_max_over_mean": jnp.zeros((), jnp.float32)}


def count_loads(counters: dict, loads: jax.Array) -> dict:
    """``counters`` after a call whose expert layers took ``loads``."""
    total = counters["loads"] + loads
    n, top = total.sum(), total.max()
    return {
        "loads": total, "expert_tokens": n, "expert_load_max": top,
        "experts_hit": counters["experts_hit"]
        + (loads > 0).sum().astype(jnp.int32),
        "expert_load_max_over_mean": jnp.where(
            n > 0, top * total.size / jnp.maximum(n, 1), 0.0
        ).astype(jnp.float32),
    }


def _tile_rows(tokens: int) -> int:
    """Rows of one grouped-product tile: the token count rounded up to
    the bfloat16 sublane tile, at most 128."""
    return min(128, -(-tokens // 16) * 16)


def _sorted_assignments(idx: jax.Array, gate: jax.Array, cfg: RoutedConfig
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``idx``, ``gate`` ``[T, k]`` sorted by held expert (a stable
    sort; what landed elsewhere, and an ``idx`` of -1, sorts last):
    each assignment's token ``[T * k]`` int32, its gate, and how many
    each held expert took ``[held]`` int32."""
    k, held = idx.shape[1], cfg.n_held
    local = idx - cfg.first
    on = (local >= 0) & (local < held)
    key = jnp.where(on, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    loads = (key[:, None] == jnp.arange(held)[None]).sum(0).astype(jnp.int32)
    return (order // k).astype(jnp.int32), gate.reshape(-1)[order], loads


def held_expert_ffn(x: jax.Array, idx: jax.Array, gate: jax.Array,
                    experts: dict, layer, cfg: RoutedConfig
                    ) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum.

    ``x [T, M]`` (``M`` is the stacks' input width: the model's, or a
    latent's); ``idx``, ``gate`` ``[T, k]`` from the router;
    ``experts`` holds ``we_gate``, ``we_up`` ``[L, held, M, F]`` and
    ``we_down`` ``[L, held, F, M]`` (``cfg.form`` "relu2": ``we_up`` and
    ``we_down`` alone) for ALL layers and ``layer`` picks one (a traced
    index: ``experts[layer, e]`` is read in place, never a per-layer
    slice, which would be copied whole). Returns ``(y [T, M] float32,
    loads [held] int32)``: the gate-weighted sum over the assignments
    that landed on held experts, and how many each held expert took.

    Assignments are sorted by held expert and run as a grouped product
    over row tiles of ``_tile_rows(T)``. On a TPU that is ONE Pallas
    kernel (:func:`held_expert_kernel`, ``ops/grouped_ffn.py``) whose
    weight reads run on from expert to expert; elsewhere the ``while``
    loop of :func:`held_expert_loop`, which is also the oracle the
    kernel is tested against. The backend chooses, nothing else does.
    """
    if jax.default_backend() == "tpu":
        return held_expert_kernel(x, idx, gate, experts, layer, cfg)
    return held_expert_loop(x, idx, gate, experts, layer, cfg)


def _expert_stacks(experts: dict, cfg: RoutedConfig) -> tuple:
    """The expert's matrices in the order of its products."""
    names = (("we_up", "we_down") if cfg.form == "relu2"
             else ("we_gate", "we_up", "we_down"))
    return tuple(experts[name] for name in names)


def held_expert_kernel(x: jax.Array, idx: jax.Array, gate: jax.Array,
                       experts: dict, layer, cfg: RoutedConfig, *,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """:func:`held_expert_ffn` through ``grouped_ffn.grouped_ffn``: one
    kernel over the (row tile, expert) pairs that hold an assignment,
    ``x`` and ``y`` resident in VMEM, each expert's products fused, the
    gate applied in float32, one rounding to the products' dtype before
    the float32 sum. Its block sizes follow from ``T``, ``M``, ``F``,
    the tile and the dtype (``grouped_ffn.f_block``). ``interpret``:
    the tests' way to run it off the TPU."""
    from dlrover_tpu.ops.grouped_ffn import grouped_ffn

    tm = _tile_rows(x.shape[0])
    tok_s, gate_s, loads = _sorted_assignments(idx, gate, cfg)
    pad = (0, -tok_s.shape[0] % tm)
    y = grouped_ffn(x, jnp.pad(tok_s, pad), jnp.pad(gate_s, pad),
                    _expert_stacks(experts, cfg), layer, loads,
                    form=cfg.form, tm=tm, interpret=interpret)
    return y, loads


def held_expert_loop(x: jax.Array, idx: jax.Array, gate: jax.Array,
                     experts: dict, layer, cfg: RoutedConfig
                     ) -> tuple[jax.Array, jax.Array]:
    """:func:`held_expert_ffn` as a ``while`` over (expert, tile) pairs
    whose trip count is data: expert ``e``'s rows are walked in tiles,
    each picked by a one-hot product, passed through the expert (its
    SwiGLU, ReGLU or squared ReLU) and added back through the transposed
    one-hot. An expert that got no token reads no weight."""
    T, M = x.shape
    tm = _tile_rows(T)
    tok_s, gate_s, loads = _sorted_assignments(idx, gate, cfg)
    tok_s = jnp.pad(tok_s, (0, tm))
    gate_s = jnp.pad(gate_s, (0, tm))
    ends = jnp.cumsum(loads)
    starts = ends - loads
    tiles = -(-loads // tm)                        # tiles of expert e
    tile_ends = jnp.cumsum(tiles)
    tokens = jnp.arange(T, dtype=jnp.int32)

    def tile(t, y):
        e = jnp.searchsorted(tile_ends, t, side="right").astype(jnp.int32)
        off = starts[e] + (t - (tile_ends[e] - tiles[e])) * tm
        rows = off + jnp.arange(tm, dtype=jnp.int32)
        valid = rows < ends[e]
        tok = jax.lax.dynamic_slice(tok_s, (off,), (tm,))
        g = jnp.where(valid, jax.lax.dynamic_slice(gate_s, (off,), (tm,)), 0)
        pick = ((tok[:, None] == tokens[None]) & valid[:, None]).astype(x.dtype)
        xt = jnp.einsum("rt,tm->rm", pick, x)

        def w(name):
            stack = experts[name]
            return jax.lax.dynamic_slice(
                stack, (layer, e, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

        if cfg.form == "relu2":
            out = relu2(xt, w("we_up"), w("we_down"))
        else:
            out = swiglu(xt, w("we_gate"), w("we_up"), w("we_down"),
                         jax.nn.relu if cfg.form == "reglu" else jax.nn.silu)
        out = (out.astype(jnp.float32) * g[:, None]).astype(x.dtype)
        return y + jnp.einsum("rt,rm->tm", pick, out,
                              preferred_element_type=jnp.float32)

    y = jax.lax.fori_loop(0, tile_ends[-1], tile,
                          jnp.zeros((T, M), jnp.float32))
    return y, loads
