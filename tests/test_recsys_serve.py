"""Sparse serving path: train_recsys checkpoint -> serve_recsys scoring.

Reference analog: tfplus serving restores the KvVariable table from a TF
checkpoint; here the C++ table + dense tower round-trip through the flash
checkpoint and the restored model must still KNOW the synthetic signal it
memorized (accuracy well above chance), not merely reload row counts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.timeout(300)
def test_train_then_serve_roundtrip(tmp_ipc_dir, tmp_path):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
    })
    train = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "train_recsys.py"),
         "--steps", "200", "--batch", "128", "--id-space", "20000",
         "--ckpt-dir", str(tmp_path / "ckpt"),
         "--result-file", str(tmp_path / "train.json")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=240,
    )
    assert train.returncode == 0, train.stderr[-2000:]
    rows = json.load(open(tmp_path / "train.json"))["table_rows"]

    env["DLROVER_TPU_IPC_DIR"] = str(tmp_path / "ipc2")
    serve = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "serve_recsys.py"),
         "--ckpt-dir", str(tmp_path / "ckpt"), "--id-space", "20000",
         "--requests", "512", "--batch", "128",
         "--result-file", str(tmp_path / "serve.json")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=240,
    )
    assert serve.returncode == 0, serve.stderr[-2000:]
    out = json.load(open(tmp_path / "serve.json"))
    assert out["table_rows"] == rows          # every row restored
    assert out["restored_step"] == 200
    # the parity signal memorized in the embeddings survived the
    # round-trip; chance is 0.5
    assert out["accuracy"] > 0.8, out


@pytest.mark.timeout(300)
def test_train_sharded_table_e2e(tmp_path):
    """BASELINE config 5 shape: the same training loop over a 2-shard
    embedding service (spawned server processes), learning the signal
    and checkpointing across shards."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
        "JAX_PLATFORMS": "cpu",
    })
    train = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "train_recsys.py"),
         "--steps", "150", "--batch", "128", "--id-space", "20000",
         "--table-shards", "2",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--incremental-ckpt",
         # first_loss is the loss at the FIRST log point: at interval 50
         # the model has already converged by then (≈0.077) and the
         # decreasing-loss assertion compares converged noise against
         # converged noise. Interval 25 samples genuinely-early training
         # (≈0.195 on this seed), giving the assertion a real margin.
         "--log-interval", "25",
         "--result-file", str(tmp_path / "train.json")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=240,
    )
    assert train.returncode == 0, train.stderr[-2000:]
    out = json.load(open(tmp_path / "train.json"))
    assert out["table_rows"] > 1000
    assert out["last_loss"] < out["first_loss"]
    # sharded incremental checkpoints landed (one chain per shard)
    shard_dirs = os.listdir(tmp_path / "ckpt" / "embedding-shards")
    assert sorted(shard_dirs) == ["n2-s0", "n2-s1"]
