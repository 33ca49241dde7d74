"""The least bytes and operations of the serving engine's two programs for
the latent-attention, routed-expert family (a configuration file with
DeepSeek-V3's keys: openPangu-Ultra-MoE), one chip's share.

Needed means: every held matrix that is not a routed expert read once a call
in bfloat16 (the dtype the weights rest in); a routed expert's three matrices
read once for each (layer, step) in which at least one token was routed to it
(``experts_hit``, the program's counter: the grouped product skips an expert
with no token, so the held experts' 6 GB are NOT all needed every step); the
LIVE latent rows read once (``kv_lora_rank + qk_rope_head_dim`` numbers a
token a layer, the tokens a slot has so far) and the new rows written once;
2 FLOPs a weight a token, with ``expert_tokens`` (the program's counter:
token-expert assignments that landed on held experts) for the routed part;
attention in the ABSORBED form for a decode step (scores over rank + rope,
values over rank, ``W_kvb`` once a token) and in the EXPANDED form for a
prefill chunk (``W_kvb`` once a live key, scores over nope + rope, values
over ``v_head_dim``, the causal part only). The output head runs for every
decode token and for a chunk's last token only. The embedding lookup is a
gather of the tokens' rows. ``cfg`` is the configuration file's dict.
"""

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Parameters of each part, as held here."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    w_kvb = cfg["kv_lora_rank"] * h * (nope + v)
    attention = (e * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
                 + e * (cfg["kv_lora_rank"] + rope) + w_kvb + h * v * e)
    dense_layers = cfg["deployment"]["dense_layers_held"]
    return {
        "attention": attention, "w_kvb": w_kvb,
        "dense_ffn": 3 * e * cfg["intermediate_size"],
        "shared": 3 * e * cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        "router": e * cfg["published"]["n_routed_experts"],
        "expert": 3 * e * cfg["moe_intermediate_size"],
        "head": e * cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"], "dense_layers": dense_layers,
        "expert_layers": cfg["num_hidden_layers"] - dense_layers,
        "row": cfg["kv_lora_rank"] + rope,
    }


def _body(s: dict) -> int:
    """Held weights a token passes outside the routed experts and the
    head."""
    return (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_ffn"]
            + s["expert_layers"] * (s["shared"] + s["router"]))


def held_parameters(cfg: dict) -> int:
    """Everything resident: body, held experts, embedding and head."""
    s = sizes(cfg)
    return (_body(s) + s["expert_layers"] * cfg["n_routed_experts"]
            * s["expert"] + 2 * s["head"])


def decode_block(cfg: dict, slots: float, n_steps: int, context: float,
                 expert_tokens: float, experts_hit: float) -> dict:
    """``n_steps`` steps for ``slots`` decoding slots with ``context`` live
    tokens each; the two counters are the block's totals."""
    s = sizes(cfg)
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    attend = s["layers"] * 2 * h * context * (s["row"] + rank)
    tokens = slots * n_steps
    return {
        "flops": (tokens * (2.0 * (_body(s) + s["head"]) + attend)
                  + 2.0 * expert_tokens * s["expert"]),
        "bytes": BF16 * (
            n_steps * (_body(s) + s["head"])
            + experts_hit * s["expert"]
            + tokens * (context + 1) * s["layers"] * s["row"]),
    }


def prefill_chunk(cfg: dict, tokens: int, context: int,
                  expert_tokens: float, experts_hit: float) -> dict:
    """One chunk of ``tokens`` prompt tokens behind ``context`` tokens that
    earlier chunks of the same prompt left in the row."""
    s = sizes(cfg)
    h = cfg["num_attention_heads"]
    pair = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
    pairs = tokens * context + tokens * (tokens + 1) / 2
    live = context + tokens
    attend = s["layers"] * (2.0 * h * pair * pairs
                            + 2.0 * live * s["w_kvb"])
    body = _body(s) - s["layers"] * s["w_kvb"]   # W_kvb: counted a live key
    return {
        "flops": (2.0 * (tokens * body + s["head"]) + attend
                  + 2.0 * expert_tokens * s["expert"]),
        "bytes": BF16 * (_body(s) + s["head"] + experts_hit * s["expert"]
                         + live * s["layers"] * s["row"]),
    }


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call["flops"] / peak["bf16_flops"],
               call["bytes"] / peak["hbm_bytes_per_s"])
