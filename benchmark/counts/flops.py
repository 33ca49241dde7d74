"""Operations the model needs, from its shapes alone.

Forward and backward of one token of a decoder-only transformer, the way
the `on-chip-measurement` guide defines model utilization: the matrix
multiplications the mathematics needs (2 FLOPs per multiply-add), the
backward pass twice the forward, recomputed operations NOT counted, the
causal half of attention only. ``cfg`` is a benchmark configuration file's
dict (``n_embd``, ``n_layer``, ``n_inner``, ``vocab_size``).
"""


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix multiplication per token: the
    four attention projections and the two FFN matrices of every layer,
    and the output head (untied here). The embedding lookup is a gather."""
    d, ff = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff) + d * cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token's forward pass inside a causal sequence of ``seq``: the
    mean query sees (seq + 1) / 2 keys, for QK^T and for PV."""
    attention = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * (seq + 1) / 2
    return 2.0 * matmul_params(cfg) + attention


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); no recompute."""
    return 3.0 * forward_flops_per_token(cfg, seq)
