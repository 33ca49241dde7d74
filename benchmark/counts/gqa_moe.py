"""The least bytes and operations of the serving engine's two programs for
the grouped-query, routed-expert, block-diffusion family (a configuration
file with Qwen3-MoE's keys and ``assumed.block_length`` /
``denoising_steps``: SDAR-30B-A3B-Chat), whole layers on one chip.

A decode call runs ``blocks`` blocks: ``denoise_passes`` passes that end in
the output head and ``store_passes`` that do not (their logits are not read),
each over ``block_length`` rows a slot. Needed means: every matrix outside
the routed experts and the head read once a PASS in bfloat16 (the dtype the
weights rest in), the head once a denoising pass; a routed expert's three
matrices read once for each (layer, pass) in which at least one row was
routed to it (``experts_hit``, the program's counter: the grouped product
skips an expert with no row); the LIVE key/value rows read once a pass (2 x
``num_key_value_heads`` x ``head_dim`` numbers a token a layer, the tokens a
slot has so far) and the block's rows written once a pass; 2 FLOPs a weight a
row, with ``expert_tokens`` (the program's counter: row-expert assignments)
for the routed part; attention's two products over the live rows and the
block. A prefill chunk reads everything once, runs the head for its last
token only, and attends block-causally (each query sees its context and its
own block to the end). The embedding lookup is a gather of the tokens' rows.
``cfg`` is the configuration file's dict.
"""

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Parameters of each part."""
    e, hd = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "attention": 2 * e * h * hd + 2 * e * g * hd,
        "router": e * cfg["num_experts"],
        "expert": 3 * e * cfg["moe_intermediate_size"],
        "head": e * cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "row": 2 * g * hd,            # a token's keys and values, a layer
        "scores": 2 * 2 * h * hd,     # FLOPs a (query, key) pair, a layer
        "block": cfg["assumed"]["block_length"],
    }


def _body(s: dict) -> int:
    """Weights a row passes outside the routed experts and the head."""
    return s["layers"] * (s["attention"] + s["router"])


def held_parameters(cfg: dict) -> int:
    """Every matrix resident: body, experts, embedding and head (the
    norms' scales are not counted)."""
    s = sizes(cfg)
    return (_body(s) + s["layers"] * cfg["num_experts"] * s["expert"]
            + 2 * s["head"])


def denoise_call(cfg: dict, slots: float, denoise_passes: int,
                 store_passes: int, context: float, expert_tokens: float,
                 experts_hit: float) -> dict:
    """One decode call for ``slots`` decoding slots with ``context`` live
    tokens each; the two counters are the call's totals."""
    s = sizes(cfg)
    passes = denoise_passes + store_passes
    rows = slots * s["block"]                      # a pass
    attend = s["layers"] * s["scores"] * (context + s["block"])
    return {
        "flops": (passes * rows * (2.0 * _body(s) + attend)
                  + denoise_passes * rows * 2.0 * s["head"]
                  + 2.0 * expert_tokens * s["expert"]),
        "bytes": BF16 * (
            passes * _body(s) + denoise_passes * s["head"]
            + experts_hit * s["expert"]
            + passes * slots * (context + s["block"]) * s["layers"]
            * s["row"]),
    }


def prefill_chunk(cfg: dict, tokens: int, context: int,
                  expert_tokens: float, experts_hit: float) -> dict:
    """One chunk of ``tokens`` prompt tokens (whole blocks) behind
    ``context`` tokens that earlier chunks of the same prompt left in the
    row."""
    s = sizes(cfg)
    # query i sees the context and the chunk up to the end of its block
    pairs = tokens * context + tokens * (tokens + s["block"]) / 2
    return {
        "flops": (2.0 * (tokens * _body(s) + s["head"])
                  + s["layers"] * s["scores"] * pairs
                  + 2.0 * expert_tokens * s["expert"]),
        "bytes": BF16 * (_body(s) + s["head"] + experts_hit * s["expert"]
                         + (context + tokens) * s["layers"] * s["row"]),
    }


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call["flops"] / peak["bf16_flops"],
               call["bytes"] / peak["hbm_bytes_per_s"])
