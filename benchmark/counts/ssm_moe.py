"""The least bytes and operations of the serving engine's two programs for
the state-space / latent-expert family (a configuration file with
``nemotron_h``'s keys: the layers, experts and vocabulary rows held).

Needed means: every held matrix OUTSIDE the routed experts read once a call
in bfloat16 (the dtype the weights rest in; of the embedding the tokens'
rows, not the table); a routed expert's two matrices once for each expert
layer and step in which a real token reached it (``experts_hit``, the
program's counter: a token that is not real reaches none); a Mamba-2 layer's
float32 state and its convolution window read and written once for every
LIVE row of a decode step (``ssm_row_steps``, the program's counter: real
tokens x Mamba-2 layers that entered a state; an idle slot, a row past its
budget leave theirs alone) and once for a chunk; the attention layers' key
and value rows a live row sees (``context_tokens``, the sum of the live
rows' positions), never a row of ``max_len``. 2 FLOPs a weight a real token
(``expert_tokens`` assignments pass one expert each); the scan as the
recurrence (state update and readout: 4 a state entry a token), which is
less than its chunked form; attention's scores and values 4 a key a head
dim. The head runs for every decode token and for a chunk's last token.
``cfg`` is the configuration file's dict.
"""

BF16, F32 = 2, 4


def sizes(cfg: dict) -> dict:
    """Parameters of each part, as held here, and the caches' sizes."""
    e = cfg["hidden_size"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = heads * p
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    n_all = cfg.get("published", {}).get("n_routed_experts",
                                         cfg["n_routed_experts"])
    pattern = cfg["hybrid_override_pattern"]
    return {
        "mamba": (e * (inner + conv + heads) + inner * e
                  + conv * (cfg["conv_kernel"] + 1)),
        "attention": 2 * e * h * d + 2 * e * g * d,
        # an expert layer outside its routed experts: router, the two
        # latent projections, the shared expert
        "expert_layer": (e * n_all + 2 * e * lat + 2 * e
                         * cfg["moe_shared_expert_intermediate_size"]),
        "expert": 2 * lat * f,
        "experts_held": cfg["n_routed_experts"],
        "head": e * cfg["vocab_size"],
        "mamba_layers": pattern.count("M"),
        "expert_layers": pattern.count("E"),
        "attention_layers": pattern.count("*"),
        "state": heads * p * cfg["ssm_state_size"],
        "window": (cfg["conv_kernel"] - 1) * conv,
        "row": g * d, "query": h * d,
    }


def _body(s: dict) -> int:
    """Held weights a token passes outside the routed experts and the
    head."""
    return (s["mamba_layers"] * s["mamba"]
            + s["attention_layers"] * s["attention"]
            + s["expert_layers"] * s["expert_layer"])


def held_parameters(cfg: dict) -> int:
    """Every held matrix: body, routed experts, embedding and head (norm
    scales, the decay's and the skip's vectors and the score bias apart)."""
    s = sizes(cfg)
    return (_body(s) + s["expert_layers"] * s["experts_held"] * s["expert"]
            + 2 * s["head"])


def state_bytes_per_slot(cfg: dict) -> int:
    """What a slot keeps whatever its context: a float32 state and a
    bfloat16 window a Mamba-2 layer."""
    s = sizes(cfg)
    return s["mamba_layers"] * (F32 * s["state"] + BF16 * s["window"])


def _state_traffic(s: dict, row_steps: float) -> dict:
    """State and window read and written for ``row_steps`` (real tokens x
    Mamba-2 layers), and the recurrence's operations."""
    return {"flops": 4.0 * row_steps * s["state"],
            "bytes": 2.0 * row_steps * (F32 * s["state"]
                                        + BF16 * s["window"])}


def decode_block(cfg: dict, n_steps: int, ssm_row_steps: float,
                 experts_hit: float, expert_tokens: float,
                 context_tokens: float) -> dict:
    """``n_steps`` decode steps whose live rows the counters describe (a
    decode_block span's fields)."""
    s = sizes(cfg)
    live = ssm_row_steps / s["mamba_layers"]
    state = _state_traffic(s, ssm_row_steps)
    keys = context_tokens * s["attention_layers"]
    return {
        "flops": (live * 2.0 * (_body(s) + s["head"])
                  + expert_tokens * 2.0 * s["expert"] + state["flops"]
                  + 4.0 * s["query"] * keys),
        "bytes": (BF16 * (n_steps * (_body(s) + s["head"])
                          + experts_hit * s["expert"]
                          + live * cfg["hidden_size"]
                          + 2 * s["row"] * (keys + live
                                            * s["attention_layers"]))
                  + state["bytes"]),
    }


def prefill_chunk(cfg: dict, tokens: int, context: int, experts_hit: float,
                  expert_tokens: float) -> dict:
    """One chunk of ``tokens`` prompt tokens behind ``context`` tokens that
    earlier chunks of the same prompt left in the row."""
    s = sizes(cfg)
    state = _state_traffic(s, tokens * s["mamba_layers"])
    # each query sees the row up to itself
    keys = s["attention_layers"] * tokens * (context + (tokens + 1) / 2)
    return {
        "flops": (2.0 * (tokens * _body(s) + s["head"])
                  + expert_tokens * 2.0 * s["expert"] + state["flops"]
                  + 4.0 * s["query"] * keys),
        "bytes": (BF16 * (_body(s) + s["head"] + experts_hit * s["expert"]
                          + tokens * cfg["hidden_size"]
                          + s["attention_layers"] * 2 * s["row"]
                          * (context + tokens))
                  # the state once a chunk, not once a token
                  + state["bytes"] / tokens),
    }


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call["flops"] / peak["bf16_flops"],
               call["bytes"] / peak["hbm_bytes_per_s"])
