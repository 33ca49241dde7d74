"""The least bytes and operations of the serving engine's two programs for
the windowed-and-full family with post-norms, a sigmoid router, a SHARED
expert and a leading DENSE layer (a configuration file with K-EXAONE's keys:
``layer_types``, ``sliding_windows``, ``mlp_layer_types``, ``num_experts``
held of ``published.num_experts``), one chip's share of a layer.

Needed means: every matrix outside the routed experts read once a decode
STEP (once a chunk) in bfloat16, the dtype the weights rest in: attention,
the router at its PUBLISHED width, the shared expert, the dense layers'
FFN, the head; a routed expert's three matrices once for each layer and
step in which a real token reached it (``experts_hit``, the program's
counter: the grouped product skips an expert with no row; only the experts
HELD are counted, the others' part is computed nowhere); a FULL layer's
key/value rows up to each live row's position (``context_tokens``: the live
rows' positions summed over the block's steps) and a WINDOWED layer's up to
``min(position, window)`` (``window_keys``: a ring holds no more), 2 x
``num_key_value_heads`` x ``head_dim`` numbers a key a layer, the new token's
own row written and read; 2 FLOPs a weight a row, with ``expert_tokens`` (the
program's counter: row-expert assignments that landed on a held expert) for
the routed part; attention's two products over the keys seen. ``row_steps``
is the live row-steps (real tokens) and ``ring_wrapped_row_steps`` those past
the window: a query at position ``p`` sees ``p + 1`` keys of a full layer and
``min(p + 1, window)`` of a windowed one. A prefill chunk reads everything
once, runs the head for its last token only, and attends causally, a windowed
layer no further back than its window (of the ``min(context, window) +
tokens`` keys the program's wide form multiplies, a query needs ``window``).
The embedding lookup is a gather of the tokens' rows. ``cfg`` is the
configuration file's dict.
"""

# the larger of operations over peak FLOP/s and bytes over peak bytes/s:
# the windowed-and-full family's, which this family's readers call here
from benchmark.counts.swa_moe import least_seconds  # noqa: F401

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Parameters of each part, and how many layers of each kind."""
    e, hd = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    windowed = sum(1 for w in cfg["sliding_windows"] if w)
    dense = sum(1 for t in cfg["mlp_layer_types"] if t == "dense")
    router_width = cfg.get("published", {}).get("num_experts",
                                                cfg["num_experts"])
    expert = 3 * e * cfg["moe_intermediate_size"]
    return {
        "attention": 2 * e * h * hd + 2 * e * g * hd,
        "router": e * router_width,
        "expert": expert,
        "shared": cfg["num_shared_experts"] * expert,
        "dense_ffn": 3 * e * cfg["intermediate_size"],
        "head": e * cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "dense": dense,
        "sparse": cfg["num_hidden_layers"] - dense,
        "windowed": windowed,
        "full": cfg["num_hidden_layers"] - windowed,
        "window": cfg["sliding_window"],
        "row": 2 * g * hd,            # a token's keys and values, a layer
        "scores": 2 * 2 * h * hd,     # FLOPs a (query, key) pair, a layer
    }


def _body(s: dict) -> int:
    """Weights a row passes outside the routed experts and the head."""
    return (s["layers"] * s["attention"] + s["dense"] * s["dense_ffn"]
            + s["sparse"] * (s["router"] + s["shared"]))


def held_parameters(cfg: dict) -> int:
    """Every matrix resident: body, the experts held, embedding and head
    (the norms' scales and the router's bias are not counted)."""
    s = sizes(cfg)
    return (_body(s) + s["sparse"] * cfg["num_experts"] * s["expert"]
            + 2 * s["head"])


def cache_bytes(cfg: dict, slots: int, max_len: int) -> dict:
    """What the slots' cache holds: full rows, rings, and what full-length
    rows in the rings' place would."""
    s = sizes(cfg)
    return {"full_rows": BF16 * s["full"] * slots * max_len * s["row"],
            "rings": BF16 * s["windowed"] * slots * s["window"] * s["row"],
            "rows_in_place_of_rings": (BF16 * s["windowed"] * slots * max_len
                                       * s["row"])}


def decode_block(cfg: dict, n_steps: int, row_steps: float,
                 experts_hit: float, expert_tokens: float,
                 context_tokens: float, window_keys: float,
                 ring_wrapped_row_steps: float) -> dict:
    """``n_steps`` decode steps whose live rows the counters describe (a
    decode_block span's fields)."""
    s = sizes(cfg)
    # keys seen, the query's own among them, summed over layers
    keys = (s["full"] * (context_tokens + row_steps)
            + s["windowed"] * (window_keys + row_steps
                               - ring_wrapped_row_steps))
    return {
        "flops": (row_steps * 2.0 * (_body(s) + s["head"])
                  + expert_tokens * 2.0 * s["expert"]
                  + s["scores"] * keys),
        "bytes": BF16 * (n_steps * (_body(s) + s["head"])
                         + experts_hit * s["expert"]
                         + row_steps * cfg["hidden_size"]
                         + s["row"] * (keys + row_steps * s["layers"])),
    }


def prefill_chunk(cfg: dict, tokens: int, context: int, experts_hit: float,
                  expert_tokens: float) -> dict:
    """One chunk of ``tokens`` prompt tokens behind ``context`` tokens that
    earlier chunks of the same prompt left in the row."""
    s = sizes(cfg)
    window = s["window"]
    full_pairs = tokens * context + tokens * (tokens + 1) / 2
    windowed_pairs = sum(min(context + t + 1, window) for t in range(tokens))
    # what a layer must read of its cache: the keys before the chunk that
    # its first query sees
    behind = (s["full"] * context
              + s["windowed"] * min(context, window - 1))
    return {
        "flops": (2.0 * (tokens * _body(s) + s["head"])
                  + expert_tokens * 2.0 * s["expert"]
                  + s["scores"] * (s["full"] * full_pairs
                                   + s["windowed"] * windowed_pairs)),
        "bytes": BF16 * (_body(s) + s["head"] + experts_hit * s["expert"]
                         + tokens * cfg["hidden_size"]
                         + s["row"] * (behind + tokens * s["layers"])),
    }
