"""The one place where a benchmark configuration file meets the program's
own preset: the file holds the configuration as it is run, so the preset it
names must say the same, or the run stops before it measures anything."""

from __future__ import annotations

import dataclasses


def tiny_config() -> dict:
    """The rehearsal's configuration file: the program's ``tiny`` preset
    read as a GPT-2."""
    return {"n_embd": 64, "n_layer": 2, "n_head": 4, "vocab_size": 512,
            "n_positions": 128, "n_inner": 176,
            "layer_norm_epsilon": 1e-5}


def program_config(cfg_file: dict, rehearse: bool):
    """The program's ``TransformerConfig`` for a configuration file."""
    from dlrover_tpu.models import transformer as tfm

    if rehearse:
        return dataclasses.replace(tfm.CONFIGS["tiny"], variant="gpt2")
    base = tfm.CONFIGS[cfg_file["program_model"]]
    runs = {"n_embd": base.d_model, "n_layer": base.n_layers,
            "n_head": base.n_heads, "vocab_size": base.vocab_size,
            "n_positions": base.max_seq_len, "n_inner": base.d_ff}
    file_says = dict(cfg_file, n_inner=cfg_file.get("n_inner")
                     or 4 * cfg_file["n_embd"])
    for key, value in runs.items():
        if file_says[key] != value:
            raise SystemExit(f"config file {key}={file_says[key]} but the "
                             f"program runs {value}")
    return base
