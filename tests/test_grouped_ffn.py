"""The held experts' grouped kernel (``ops/grouped_ffn.py``) in interpret
mode on the CPU, against the ``while`` loop it replaces on a TPU
(``moe.held_expert_loop``) and against a dense per-assignment sum.

Interpret mode is slow: every case is a few dozen rows wide.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import grouped_ffn, moe

TOL = 2e-5


def _experts(key, held, M, F, form, layers=2):
    ks = jax.random.split(key, 3)
    ex = {"we_up": jax.random.normal(ks[0], (layers, held, M, F)) / 8,
          "we_down": jax.random.normal(ks[1], (layers, held, F, M)) / 6}
    if form != "relu2":
        ex["we_gate"] = jax.random.normal(ks[2], (layers, held, M, F)) / 8
    return ex


def _dense(x, idx, gate, ex, layer, rcfg):
    """Every held expert over every token, weighted by the gates of the
    assignments that chose it."""
    want = jnp.zeros(x.shape, jnp.float32)
    for e in range(rcfg.n_held):
        g = jnp.where(idx == e + rcfg.first, gate, 0.0).sum(-1)
        w = {k: v[layer, e] for k, v in ex.items()}
        if rcfg.form == "relu2":
            out = moe.relu2(x, w["we_up"], w["we_down"])
        elif rcfg.form == "reglu":
            out = (jax.nn.relu(x @ w["we_gate"]) * (x @ w["we_up"])
                   ) @ w["we_down"]
        else:
            out = moe.swiglu(x, w["we_gate"], w["we_up"], w["we_down"])
        want = want + g[:, None] * out
    return want


def _routed(key, T, M, rcfg, masked=False):
    kx, kr, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (T, M))
    idx, gate = moe.sigmoid_topk_route(
        x, jax.random.normal(kr, (M, rcfg.n_experts)) / 8, rcfg,
        bias=0.3 * jax.random.normal(kb, (rcfg.n_experts,)))
    if masked:   # rows that are not real reach no expert
        idx = jnp.where(jnp.arange(T)[:, None] % 3 == 1, -1, idx)
    return x, idx, gate


def _one_expert(key, T, M, rcfg, expert):
    """Every token's first choice is ``expert``; its other choices lie
    outside the held share."""
    x = jax.random.normal(key, (T, M))
    outside = [e for e in range(rcfg.n_experts)
               if not rcfg.first <= e < rcfg.first + rcfg.n_held]
    idx = jnp.tile(jnp.asarray([[expert, *outside[:rcfg.top_k - 1]]],
                               jnp.int32), (T, 1))
    gate = jax.random.uniform(jax.random.fold_in(key, 1), (T, rcfg.top_k))
    return x, idx, gate


# name: (T, M, F, RoutedConfig, how the rows are routed, F blocked)
CASES = {
    # (a) the three cells' forms at reduced sizes
    "swiglu_all_held_k8": (24, 64, 32, moe.RoutedConfig(
        n_experts=16, top_k=8), "router", False),
    "relu2_latent_share_k22_some_rows_not_real": (
        12, 32, 48, moe.RoutedConfig(
            n_experts=64, top_k=22, first=16, held=16, scaling=5.0,
            form="relu2"), "masked", False),
    "swiglu_16_of_256_held_F_in_blocks": (40, 128, 384, moe.RoutedConfig(
        n_experts=256, top_k=8, first=32, held=16, scaling=2.5),
        "router", True),
    "reglu_all_held_k6": (24, 64, 32, moe.RoutedConfig(
        n_experts=16, top_k=6, form="reglu"), "router", False),
    "reglu_F_in_blocks_some_rows_not_real": (20, 128, 256, moe.RoutedConfig(
        n_experts=8, top_k=3, form="reglu"), "masked", True),
    # (b) the edges
    "an_expert_with_no_row": (3, 64, 32, moe.RoutedConfig(
        n_experts=16, top_k=2, first=0, held=8), "router", False),
    "every_row_on_one_expert_three_tiles": (300, 32, 32, moe.RoutedConfig(
        n_experts=16, top_k=4, first=4, held=4), "one:5", False),
    "rows_not_a_multiple_of_the_tile": (19, 64, 32, moe.RoutedConfig(
        n_experts=8, top_k=3), "router", False),
    "one_token": (1, 64, 32, moe.RoutedConfig(
        n_experts=8, top_k=3, first=2, held=4), "router", False),
    "no_assignment_on_a_held_expert": (20, 64, 32, moe.RoutedConfig(
        n_experts=16, top_k=4, first=4, held=4), "one:12", False),
    "relu2_F_in_blocks": (20, 128, 256, moe.RoutedConfig(
        n_experts=8, top_k=3, form="relu2"), "router", True),
}


@pytest.mark.parametrize("name", CASES)
def test_the_kernel_is_the_loop_and_the_dense_sum(name, monkeypatch):
    T, M, F, rcfg, routing, blocked = CASES[name]
    key = jax.random.PRNGKey(sum(map(ord, name)))
    ex = _experts(key, rcfg.n_held, M, F, rcfg.form)
    if blocked:    # no room: the smallest block of whole lanes
        monkeypatch.setattr(grouped_ffn, "VMEM_BYTES", 0)
        assert grouped_ffn.f_block(T, M, F, 16, len(ex), 4) == 128
    if routing.startswith("one:"):
        x, idx, gate = _one_expert(key, T, M, rcfg, int(routing[4:]))
    else:
        x, idx, gate = _routed(key, T, M, rcfg, masked=routing == "masked")
    loop, loop_loads = jax.jit(
        lambda *a: moe.held_expert_loop(*a, ex, 1, rcfg))(x, idx, gate)
    got, loads = jax.jit(lambda *a: moe.held_expert_kernel(
        *a, ex, 1, rcfg, interpret=True))(x, idx, gate)
    on = (idx >= rcfg.first) & (idx < rcfg.first + rcfg.n_held)
    assert loads.dtype == jnp.int32
    assert loads.tolist() == loop_loads.tolist() == np.bincount(
        np.asarray(idx - rcfg.first)[np.asarray(on)],
        minlength=rcfg.n_held).tolist()
    assert got.dtype == jnp.float32 and got.shape == (T, M)
    assert float(jnp.abs(got - loop).max()) < TOL
    assert float(jnp.abs(got - _dense(x, idx, gate, ex, 1, rcfg)).max()) < TOL
    if name == "no_assignment_on_a_held_expert":
        assert not loads.any() and not np.asarray(got).any()
    if name == "an_expert_with_no_row":
        assert 0 in loads.tolist() and loads.sum() > 0


def test_a_traced_layer_under_scan_reads_that_layers_experts():
    rcfg = moe.RoutedConfig(n_experts=8, top_k=2, first=2, held=4)
    key = jax.random.PRNGKey(7)
    ex = _experts(key, 4, 64, 32, "swiglu", layers=3)
    x, idx, gate = _routed(key, 10, 64, rcfg)

    def scanned(fn):
        def body(carry, layer):
            y, loads = fn(x, idx, gate, ex, layer, rcfg)
            return carry + loads, y
        return jax.jit(lambda: jax.lax.scan(
            body, jnp.zeros((4,), jnp.int32), jnp.arange(3)))()

    total, ys = scanned(lambda *a: moe.held_expert_kernel(*a, interpret=True))
    loop_total, loop_ys = scanned(moe.held_expert_loop)
    assert total.tolist() == loop_total.tolist()
    assert float(jnp.abs(ys - loop_ys).max()) < TOL
    for layer in range(3):
        assert float(jnp.abs(ys[layer] - _dense(
            x, idx, gate, ex, layer, rcfg)).max()) < TOL
    assert float(jnp.abs(ys[0] - ys[1]).max()) > 0.01


@pytest.mark.parametrize("loads,tm,n_tiles", [
    ([3, 0, 5, 0, 0, 2, 9, 0], 4, 6),     # experts that end inside a tile
    ([0, 0, 0, 0], 16, 2),                # nothing landed here
    ([40, 0, 0, 1], 16, 3),               # one expert over three tiles
    ([16, 16, 16], 16, 3),                # every expert a whole tile
])
def test_the_grid_visits_every_tile_and_expert_pair_that_holds_a_row(
        loads, tm, n_tiles):
    e, tile, lo, hi, total = (np.asarray(a) for a in grouped_ffn.visits(
        jnp.asarray(loads, jnp.int32), n_tiles, tm))
    ends = np.cumsum(loads)
    want = [(int(r // tm), ex) for ex, (s, n) in enumerate(
        zip(ends - loads, loads)) for r in range(s, s + n)]
    want = sorted(set(want), key=lambda p: (p[1], p[0]))
    total = int(total)
    assert e.shape == (len(loads) + n_tiles,) and total == len(want)
    assert list(zip(tile[:total].tolist(), e[:total].tolist())) == want
    for v in range(total):
        assert (lo[v], hi[v]) == (ends[e[v]] - loads[e[v]], ends[e[v]])
    # the entries past the last visit repeat it (the grid stops before)
    last = max(total - 1, 0)
    assert (e[total:] == e[last]).all() and (tile[total:] == tile[last]).all()


def test_block_sizes_follow_from_the_shapes():
    """The three cells' calls in bfloat16: SDAR's and Nemotron's experts
    pass whole; openPangu's 31 MB matrices go in two blocks of F beside a
    decode step's 16 rows and in four beside a chunk's 512 (its ``x``
    and ``y`` resident)."""
    assert grouped_ffn.f_block(64, 2048, 768, 64, 3, 2) == 768
    assert grouped_ffn.f_block(512, 2048, 768, 128, 3, 2) == 768
    assert grouped_ffn.f_block(32, 1024, 2688, 32, 2, 2) == 2688
    assert grouped_ffn.f_block(512, 1024, 2688, 128, 2, 2) == 2688
    assert grouped_ffn.f_block(16, 7680, 2048, 16, 3, 2) == 1024
    assert grouped_ffn.f_block(512, 7680, 2048, 128, 3, 2) == 512
    assert grouped_ffn.f_block(20, 64, 32, 32, 3, 4) == 32   # under 128 lanes


def test_the_backend_alone_chooses_between_kernel_and_loop(monkeypatch):
    rcfg = moe.RoutedConfig(n_experts=8, top_k=2)
    ex = _experts(jax.random.PRNGKey(0), 8, 64, 32, "swiglu")
    x, idx, gate = _routed(jax.random.PRNGKey(1), 4, 64, rcfg)

    def program():
        return str(jax.make_jaxpr(
            lambda *a: moe.held_expert_ffn(*a, ex, 0, rcfg))(x, idx, gate))

    assert jax.default_backend() == "cpu"
    here = program()
    assert "while" in here and "pallas_call" not in here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    there = program()
    assert "pallas_call" in there and "while" not in there
    assert "held_expert_grouped_ffn" in there
