"""The least bytes and operations of the serving engine's two programs.

A decode step feeds one token to each slot that decodes; a prefill chunk
feeds up to ``prefill_len`` tokens of one prompt. Needed means: the matrix
weights read once in bfloat16 (the precision the products run in; the program
keeps float32 and converts, which is work the algorithm does not need), the
LIVE rows of the bfloat16 KV cache read once (the tokens a slot has so far,
not the cache's whole length) and the new rows written once, 2 FLOPs per
weight per token, and the attention products over the live rows. Slots that
hold no request need nothing. ``cfg`` is a benchmark configuration file's
dict; the output head runs for every decode token and for a chunk's last
token only.
"""

from benchmark.counts import flops

BF16 = 2


def _head(cfg: dict) -> int:
    return cfg["n_embd"] * cfg["vocab_size"]


def _kv_row_bytes(cfg: dict) -> int:
    """One token's keys and values over all layers."""
    return cfg["n_layer"] * 2 * cfg["n_embd"] * BF16


def decode_step(cfg: dict, slots: float, context: float) -> dict:
    """One step for ``slots`` decoding slots with ``context`` live tokens
    each."""
    weights = flops.matmul_params(cfg)
    attention = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * context
    return {
        "flops": slots * (2.0 * weights + attention),
        "bytes": (weights * BF16
                  + slots * (context + 1) * _kv_row_bytes(cfg)),
    }


def prefill_chunk(cfg: dict, tokens: int, context: int) -> dict:
    """One chunk of ``tokens`` prompt tokens behind ``context`` tokens
    that earlier chunks of the same prompt left in the cache row."""
    body = flops.matmul_params(cfg) - _head(cfg)
    # token i of the chunk sees the context and the chunk up to itself
    keys = tokens * context + tokens * (tokens + 1) / 2
    attention = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * keys
    return {
        "flops": 2.0 * (body * tokens + _head(cfg)) + attention,
        "bytes": ((body + _head(cfg)) * BF16
                  + (context + tokens) * _kv_row_bytes(cfg)),
    }


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call["flops"] / peak["bf16_flops"],
               call["bytes"] / peak["hbm_bytes_per_s"])
