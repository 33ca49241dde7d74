"""``dlrover_tpu/models/`` imports one way (DESIGN.md §23.7): read from
the source, nothing imported. Beside it, every preset walked through the
family table."""

import ast
import pathlib

import pytest

MODELS = pathlib.Path(__file__).parent.parent / "dlrover_tpu" / "models"
TREES = {p.stem: ast.parse(p.read_text()) for p in MODELS.glob("*.py")}
FAMILIES = ("latent", "hybrid")
# who a module may import of `models/`: the arrows point down only
BELOW = {
    "cache": (), "transformer": ("cache",),
    "latent": ("cache", "transformer"), "hybrid": ("cache", "transformer"),
    "decode": ("cache", "transformer", *FAMILIES),
}


def _imports(tree):
    """``(module of models/, name or None, node)`` of every import of a
    sibling, wherever it stands."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "dlrover_tpu.models":
                for a in node.names:
                    yield a.name, None, node
            elif node.module.startswith("dlrover_tpu.models."):
                for a in node.names:
                    yield node.module.split(".")[2], a.name, node
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("dlrover_tpu.models."):
                    yield a.name.split(".")[2], None, node


def _aliases(tree):
    """The names a module knows its siblings by (``tfm`` ...)."""
    out = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.ImportFrom)
                and node.module == "dlrover_tpu.models"):
            for a in node.names:
                out[a.asname or a.name] = a.name
    return out


@pytest.mark.parametrize("name", sorted(TREES))
def test_no_underscore_name_crosses_a_module_of_models(name):
    tree = TREES[name]
    taken = [f"{mod}.{what}" for mod, what, _ in _imports(tree)
             if what and what.startswith("_")]
    known = _aliases(tree)
    taken += [f"{known[n.value.id]}.{n.attr}" for n in ast.walk(tree)
              if isinstance(n, ast.Attribute) and n.attr.startswith("_")
              and not n.attr.startswith("__")
              and isinstance(n.value, ast.Name) and n.value.id in known
              and known[n.value.id] != name]
    assert not taken


@pytest.mark.parametrize("name", sorted(BELOW))
def test_the_arrows_point_down(name):
    """No family imports ``decode``; nothing imports what stands above it,
    but for the resolver (below)."""
    inside_family = {
        id(n) for f in ast.walk(TREES[name])
        if isinstance(f, ast.FunctionDef) and f.name == "family"
        for n in ast.walk(f)} if name == "transformer" else set()
    upward = [mod for mod, _, node in _imports(TREES[name])
              if mod in BELOW and mod != name and mod not in BELOW[name]
              and id(node) not in inside_family]
    assert not upward


def test_the_resolver_is_one_function_that_imports_every_family():
    """(and `test_the_arrows_point_down` finds no such import outside it)"""
    tree = TREES["transformer"]
    resolver = [f for f in ast.walk(tree)
                if isinstance(f, ast.FunctionDef) and f.name == "family"]
    assert len(resolver) == 1
    assert {mod for mod, _, _ in _imports(resolver[0])} == {
        *FAMILIES, "decode"}


def _presets():
    """The keys of ``transformer.CONFIGS``, read from its source."""
    for node in TREES["transformer"].body:
        if (isinstance(node, ast.Assign)
                and getattr(node.targets[0], "id", "") == "CONFIGS"):
            return [k.value for k in node.value.keys]
    raise AssertionError("transformer.CONFIGS is no dict literal")


@pytest.mark.parametrize("preset", _presets())
def test_the_family_table_answers_for_every_preset(preset):
    """The resolver answers, and the family's ``param_shapes`` and
    ``init_cache`` build (shapes only: the presets are published sizes)."""
    import jax

    from dlrover_tpu.models import decode
    from dlrover_tpu.models import transformer as tfm

    cfg = tfm.CONFIGS[preset]
    fam = tfm.family(cfg)
    assert fam is not None and all(
        callable(f) for f in fam[:4]) and (fam.forward is None) == (
        cfg.default_kinds or cfg.attn_kind == "heads_qk_norm"
        and not cfg.ffn_kind and cfg.generation == "autoregressive")
    shapes = fam.param_shapes(cfg)
    assert shapes == tfm.param_shapes(cfg)
    made = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: tuple(a.shape), made) == shapes
    runs = tfm.stack_runs(cfg)
    assert sum(r.n for r in runs) == cfg.n_layers
    assert {r.key for r in runs} == {
        k for k, v in shapes.items() if isinstance(v, dict)}
    blk = cfg.sparse_block if "sparse" in cfg.mixer_types else 1
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 2, 4 * blk))
    assert cache["pos"].shape == () and decode.cache_stacks(cache)
    # a kind's carried stacks hold a layer for each layer of its kind
    held = {r.kind: r.first_of_kind + r.n for r in runs}
    for stack in decode.cache_stacks(cache).values():
        assert stack.shape[0] in {
            n * (cfg.sparse_kv_heads if kind == "sparse" else 1)
            for kind, n in held.items()}
