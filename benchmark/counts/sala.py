"""The least bytes and operations of the serving engine's two programs for
the hybrid family: block-sparse attention over a compressed-key cache beside
lightning linear-attention layers (a configuration file with MiniCPM-SALA's
keys, the layers held).

Needed means: every held matrix read once a call in bfloat16 (the dtype the
weights rest in; of the embedding the tokens' rows, not the table); a
lightning layer's float32 state read and written once for every LIVE row of
a decode step (``slots x n_steps - frozen_row_steps``: an idle slot, a row
past its budget leave theirs alone) and once for a chunk; in a sparse layer
the key and value rows THE SELECTION CHOSE (``sparse_keys_selected``, the
program's counter: for each real query, sparse layer and key/value group the
keys at or before the query in its selected blocks, or all it sees under
``dense_len``) and the compressed keys the query sees, not a row of
``max_len``; a chunk reads each live row once (its 512 queries' selections
cover nearly all of them). 2 FLOPs a weight a token; attention products over
the selected keys alone (scores and values: 4 a key a head dim), the
selection's own scores over the visible compressed keys; the lightning
mixer as the recurrence (state update and readout: 4 a state entry a token),
which is less than its chunk form. The head runs for every decode token and
for a chunk's last token. ``cfg`` is the configuration file's dict.
"""

BF16, F32 = 2, 4
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def sizes(cfg: dict) -> dict:
    """Parameters of each part, as held here, and the caches' sizes."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    lh, lg, ld = (cfg["lightning_nh"], cfg["lightning_nkv"],
                  cfg["lightning_head_dim"])
    kinds = [KINDS[m] for m in cfg["mixer_types"]]
    return {
        "ffn": 3 * e * f,
        "sparse_mixer": 3 * e * h * d + 2 * e * g * d,
        "lightning_mixer": 3 * e * lh * ld + 2 * e * lg * ld,
        "head": e * cfg["vocab_size"],
        "sparse_layers": kinds.count("sparse"),
        "lightning_layers": kinds.count("lightning"),
        "state": lh * ld * ld, "heads_a_group": h // g, "head_dim": d,
        "groups": g, "row": g * d,
        "stride": cfg["assumed"]["sparse_config"]["kernel_stride"],
        "kernel": cfg["assumed"]["sparse_config"]["kernel_size"],
        "dense_len": cfg["assumed"]["sparse_config"]["dense_len"],
    }


def _body(s: dict) -> int:
    """Held weights a token passes outside the head."""
    return (s["sparse_layers"] * (s["sparse_mixer"] + s["ffn"])
            + s["lightning_layers"] * (s["lightning_mixer"] + s["ffn"]))


def held_parameters(cfg: dict) -> int:
    """Every held matrix: body, embedding and head (norm scales apart)."""
    s = sizes(cfg)
    return _body(s) + 2 * s["head"]


def _attend(s: dict, keys_selected: float, compressed_seen: float) -> dict:
    """The sparse layers' attention for queries that selected
    ``keys_selected`` keys and saw ``compressed_seen`` compressed keys,
    both summed over queries, sparse layers and key/value groups."""
    pairs = s["heads_a_group"] * s["head_dim"]
    return {"flops": 4.0 * pairs * keys_selected
            + 2.0 * pairs * compressed_seen,
            "bytes": BF16 * s["head_dim"] * (2 * keys_selected
                                             + compressed_seen)}


def decode_block(cfg: dict, slots: float, n_steps: int,
                 frozen_row_steps: float, context_tokens: float,
                 keys_selected: float) -> dict:
    """``n_steps`` steps for ``slots`` decoding slots; ``context_tokens``
    is the block's sum of its live rows' positions and ``keys_selected``
    its ``sparse_keys_selected``."""
    s = sizes(cfg)
    live = slots * n_steps - frozen_row_steps
    # a row at position p sees about p / stride compressed keys, a
    # key/value group a sparse layer
    seen = context_tokens / s["stride"] * s["sparse_layers"] * s["groups"]
    attend = _attend(s, keys_selected, seen)
    state = live * s["lightning_layers"] * s["state"]
    return {
        "flops": (live * 2.0 * (_body(s) + s["head"]) + attend["flops"]
                  + 4.0 * state),
        "bytes": (BF16 * (n_steps * (_body(s) + s["head"])
                          + live * cfg["hidden_size"]
                          + live * s["sparse_layers"] * 2 * s["row"])
                  + attend["bytes"] + 2 * F32 * state),
    }


def compressed_seen(s: dict, tokens: int, context: int) -> int:
    """Compressed keys the chunk's queries past ``dense_len`` see, summed
    over the queries (one key/value group of one layer)."""
    total = 0
    for t in range(context, context + tokens):
        if t + 1 > s["dense_len"]:
            total += (t + 1 - s["kernel"]) // s["stride"] + 1
    return total


def prefill_chunk(cfg: dict, tokens: int, context: int,
                  keys_selected: float) -> dict:
    """One chunk of ``tokens`` prompt tokens behind ``context`` tokens that
    earlier chunks of the same prompt left in the row."""
    s = sizes(cfg)
    seen = (compressed_seen(s, tokens, context) * s["sparse_layers"]
            * s["groups"])
    attend = _attend(s, keys_selected, seen)
    state = s["lightning_layers"] * s["state"]
    live = context + tokens
    return {
        "flops": (2.0 * (tokens * _body(s) + s["head"]) + attend["flops"]
                  + 4.0 * tokens * state),
        "bytes": (BF16 * (_body(s) + s["head"] + tokens * cfg["hidden_size"]
                          + s["sparse_layers"] * live * (
                              2 * s["row"] + s["row"] / s["stride"]))
                  + 2 * F32 * state),
    }


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call["flops"] / peak["bf16_flops"],
               call["bytes"] / peak["hbm_bytes_per_s"])
