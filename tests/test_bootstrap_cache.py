"""Compile-cache placement in trainer bring-up.

One rule (``parallel/compile_cache.cache_root``): where
``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory in the
checkout; the AOT artifact layer lives under it; on the CPU the XLA
persistent cache is off. These tests pin that table without
initializing any backend.
"""

from __future__ import annotations

import os

import jax
import pytest

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.parallel import compile_cache as cc
from dlrover_tpu.trainer import bootstrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def config_updates(monkeypatch):
    """Record (not apply) what setup_compilation_cache sets in JAX."""
    calls: dict = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def _on_chip(monkeypatch):
    monkeypatch.setattr(bootstrap, "_cpu_only", lambda: False)


def test_cpu_turns_the_xla_cache_off(config_updates):
    # conftest holds JAX to the CPU: that alone decides
    assert bootstrap._cpu_only()
    assert bootstrap.setup_compilation_cache() is None
    assert config_updates == {"jax_enable_compilation_cache": False}


def test_env_set_places_both_layers_and_sets_no_dir(
        config_updates, monkeypatch, tmp_path):
    _on_chip(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert bootstrap.setup_compilation_cache() == str(tmp_path)
    # JAX reads the variable itself; no directory is set in code
    assert "jax_compilation_cache_dir" not in config_updates
    assert cc.default_local_dir() == str(tmp_path / "aot")


def test_env_unset_uses_one_fixed_dir_in_the_checkout(
        config_updates, monkeypatch):
    _on_chip(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.join(REPO, ".compile_cache")
    assert bootstrap.setup_compilation_cache() == root
    assert config_updates["jax_compilation_cache_dir"] == root
    assert cc.default_local_dir() == os.path.join(root, "aot")


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_placement_does_not_move_with_job_or_process(
        monkeypatch, env_dir):
    # the path is part of XLA's cache key: one job's incarnations, its
    # parked standby and a co-started replica must all resolve the same
    # directory, whatever the job is called
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setenv(EnvKey.JOB_NAME, "jobA")
    first = cc.cache_root()
    monkeypatch.setenv(EnvKey.JOB_NAME, "jobB")
    assert cc.cache_root() == first
    assert "/tmp" not in first and str(os.getpid()) not in first


def test_client_writes_under_the_root(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    client = cc.CompileCacheClient()
    client.put("t1x8/abc", b"blob")
    assert (tmp_path / "aot" / "t1x8_abc.aot").read_bytes() == b"blob"
    assert client.get("t1x8/abc") == (b"blob", "local")


def test_no_unconditional_cache_dir_update_in_program_code():
    # the acceptance grep, kept as a test: the one place that sets the
    # directory in code is bootstrap's env-unset branch
    hits = []
    for top in ("dlrover_tpu", "examples", "bench.py"):
        path = os.path.join(REPO, top)
        files = [path] if path.endswith(".py") else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")
        ]
        for f in files:
            with open(f) as fh:
                for n, line in enumerate(fh, 1):
                    if "config.update(\"jax_compilation_cache_dir\"" \
                            in line:
                        hits.append((os.path.relpath(f, REPO), n))
    assert [h[0] for h in hits] == ["dlrover_tpu/trainer/bootstrap.py"]
