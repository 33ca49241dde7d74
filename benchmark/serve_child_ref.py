"""The serving child for a configuration that NAMES ITS REFERENCE (the
configuration file's ``reference``: a module of ``benchmark/reference/``
that makes weights a leaf at a time, ``weight(cfg, seed, layer, name)``, and
computes ``logits_many``). Started by the ``serve_gateway_ref`` driver with a
spec file; writes its answer as JSON, in the form ``serve_child`` writes.

What differs from ``serve_child``: the program's configuration is checked
against EVERY published key of the file (or the run stops); the weights are
the reference's own numbers, built into the program's stacks on the device
in the dtype the file states (bfloat16: both sides hold the same numbers);
token ids are drawn from the vocabulary rows held; `correct` compares with
the reference named. The traffic loop, the warm-up, the summary and the
sample are ``serve_child``'s.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import threading
import time

T_PROCESS = time.time()

from benchmark.serve_child import (  # noqa: E402
    drive,
    sample_and_prefill,
    summarize,
    warm_up,
)

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
    "max_seq_len": "max_position_embeddings", "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "moe_top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "vocab_size": "vocab_size",
    "n_layers": "num_hidden_layers", "first_k_dense": "first_k_dense_replace",
    "n_routed_experts": "n_routed_experts",
}
# published keys that say which kinds the program must run
KINDS = {"model_type": "pangu_ultra_moe", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "sandwich_norm": True}


def published(cfgf: dict, key: str):
    """The publication's value of ``key``: the file's, unless the file
    reduced it and keeps the original under ``published``."""
    return cfgf.get("published", {}).get(key, cfgf[key])


def program_config(cfgf: dict):
    """The program's ``TransformerConfig`` for a configuration file: the
    preset it names must hold every published value, then the share the
    file states is applied to it."""
    import dataclasses

    from dlrover_tpu.models import transformer as tfm

    if cfgf["program_model"] not in tfm.CONFIGS:
        raise SystemExit(f"the program has no preset "
                         f"{cfgf['program_model']!r}: it cannot run this "
                         "configuration")
    base = tfm.CONFIGS[cfgf["program_model"]]
    for field, key in PUBLISHED.items():
        if getattr(base, field) != published(cfgf, key):
            raise SystemExit(
                f"config file {key}={published(cfgf, key)!r} but the "
                f"program's {cfgf['program_model']} has {field}="
                f"{getattr(base, field)!r}")
    for key, value in KINDS.items():
        if cfgf[key] != value:
            raise SystemExit(f"config file {key}={cfgf[key]!r}: the "
                             f"program runs {value!r}")
    if (base.attn_kind, base.norm_kind, base.ffn_kind) != (
            "latent", "sandwich", "sigmoid_experts"):
        raise SystemExit(f"{cfgf['program_model']} is not of the kinds "
                         "this file publishes")
    if cfgf["num_nextn_predict_layers"] != 0:
        raise SystemExit("the program has no multi-token-prediction module")
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=cfgf["num_hidden_layers"],
        first_k_dense=cfgf["deployment"]["dense_layers_held"],
        experts_held=cfgf["n_routed_experts"],
        expert_first=cfgf["deployment"]["expert_first"],
        vocab_size=cfgf["vocab_size"], dtype=dtype, param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers: each
    stack is filled in place, a layer's leaf at a time, so that at most one
    float32 leaf exists beside what is kept."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.models import latent

    dt = jnp.dtype(pcfg.param_dtype)
    put = jax.jit(lambda stack, leaf, i: lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    shapes = latent.param_shapes(pcfg)
    params = {name: ref.weight(cfgf, seed, ref.TOP, name).astype(dt)
              for name in ("embed", "ln_f", "lm_head")}
    first = 0
    for key, _, n in latent.segments(pcfg):
        params[key] = {}
        for name, shape in shapes[key].items():
            stack = jnp.zeros(shape, dt)
            for i in range(n):
                stack = put(stack, ref.weight(cfgf, seed, first + i, name), i)
            params[key][name] = stack
        first += n
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if got != shapes:
        raise SystemExit(f"the reference's leaves {got} are not the "
                         f"program's {shapes}")
    return jax.block_until_ready(params)


def build(spec: dict, ref):
    """(device dict, program config, gateway); set-up's heavy half."""
    import jax

    from dlrover_tpu.gateway import Gateway
    from dlrover_tpu.serving import InferenceEngine
    from dlrover_tpu.trainer import bootstrap

    bootstrap.setup_compilation_cache()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    cfgf, serving = spec["config"], spec["serving"]
    if not spec["rehearse"] and (device["platform"] != "tpu"
                                 or device["count"] < spec["chips"]):
        raise SystemExit(f"no accelerator for this cell: {device}")
    pcfg = program_config(cfgf)
    # the engine gets the only reference to the weights: the reference
    # makes its own from the seed once the engine is freed
    weights = [program_params(ref, cfgf, spec["seed"], pcfg)]

    def engine_factory():
        return InferenceEngine(
            weights.pop(), pcfg, slots=serving["slots"],
            max_len=serving["max_len"], prefill_len=serving["prefill_len"],
            decode_block=serving["decode_block"],
            prefix_cache_entries=serving["prefix_cache_entries"],
            kv_pages=serving["kv_pages"])

    gateway = Gateway(engine_factory, replicas=1,
                      prefill_len=serving["prefill_len"])
    deadline = time.monotonic() + 900
    while not gateway.pool.ready_replicas():
        if time.monotonic() > deadline:
            raise SystemExit("the replica never became ready")
        time.sleep(0.05)
    return device, pcfg, gateway


# what a check reads that found nothing to compare: over any limit, so
# that a run whose comparison went missing is not `correct`
NOTHING_COMPARED = 1e9


def _positions(spec, sample) -> tuple[list, list]:
    """Where the engine's own logits are taken, as ``(place in the sample,
    tokens fed)``: the last ``limits.prompt_positions`` tokens of every
    sampled prompt (several, so that some are decided whatever the sample
    holds), and ``limits.tail_positions`` seeded positions of the last
    chunk of prompt plus answer."""
    import numpy as np

    from benchmark import traffic

    chunk, limits = spec["serving"]["prefill_len"], spec["limits"]
    rng = traffic.rng_for(spec["seed"], 0x7461696C)
    ends, tail = [], []
    for i, rec in enumerate(sample):
        n_prompt = len(rec["prompt"])
        ends += [(i, n) for n in range(
            max(1, n_prompt - limits["prompt_positions"] + 1), n_prompt + 1)]
        n_all = n_prompt + len(rec["result"].tokens)
        fed = np.arange((n_all - 1) // chunk * chunk + 1, n_all + 1)
        tail += [(i, n) for n in sorted(rng.choice(
            fed, size=min(limits["tail_positions"], len(fed)),
            replace=False).tolist())]
    return ends, tail


def engine_logits(spec, engine, sample, control: str) -> dict:
    """The timed engine's own float32 logits, through its chunked-prefill
    program, at :func:`_positions` (prompt plus served answer fed as a
    prompt; after the first, the prefix cache resumes each from the row at
    the last chunk boundary, so a tail position costs one chunk).
    ``{(place in the sample, tokens fed): logits}``; nothing under a
    control, whose logits the reference supplies."""
    import numpy as np

    if control:
        return {}
    out = {}
    for i, n in sorted(set(sum(_positions(spec, sample), []))):
        rec = sample[i]
        run = engine.prefill_begin(
            (rec["prompt"] + list(rec["result"].tokens))[:n])
        while not engine.prefill_step(run):
            pass
        out[i, n] = np.asarray(run.last, np.float32).reshape(-1)
    return out


def compare(spec, ref, sample, control: str, logits: dict) -> dict:
    """The reference run once over each sampled prompt plus its served
    answer, against what the engine served and the logits it gave. Per
    position ``(reading, the reference's choice margin there, the request's
    index in the pool, tokens fed)``:
      decode   at each served position, how far the served token's
          reference logit lies below the reference's best (what
          ``serve_child`` reads);
      prefill, tail   the engine's logits (:func:`engine_logits`) against
          the reference's, the largest difference in units of the reference
          logits' standard deviation.
    A closed loop goes round its pool, so a sample may hold one request
    several times: the same reading at the same place is ONE reading (a
    set), or one flipped choice would count three times.
    ``control`` puts the reference with a fault in the program's place: the
    token that puts first, and its logits, at the same positions."""
    import numpy as np

    cfgf = spec["config"]
    width = spec["serving"]["max_len"]
    seqs = []
    for rec in sample:
        seq = np.zeros((width,), np.int32)
        tokens = rec["prompt"] + list(rec["result"].tokens)
        seq[: len(tokens)] = tokens
        seqs.append(seq)
    rows_all, margins = ref.logits_many(cfgf, spec["seed"], seqs)
    low_all = (ref.logits_many(cfgf, spec["seed"], seqs, control)[0]
               if control else None)
    at_prompt, at_tail = _positions(spec, sample)
    out = {"decode": set(), "prefill": set(), "tail": set()}
    for i, rec in enumerate(sample):
        prompt, answer = rec["prompt"], list(rec["result"].tokens)
        at = np.arange(len(prompt) - 1, len(prompt) + len(answer) - 1)
        rows = np.asarray(rows_all[i][at])                # [answer, vocab]
        margin = np.minimum(np.asarray(margins[i]), 99.0)
        served = (np.asarray(low_all[i][at]).argmax(axis=-1)
                  if control else np.asarray(answer))
        gaps = rows.max(axis=-1) - rows[np.arange(len(at)), served]
        out["decode"] |= {(float(g), float(m), rec["index"], int(n) + 1)
                          for g, m, n in zip(gaps, margin[at], at)}
        for name, positions in (("prefill", at_prompt), ("tail", at_tail)):
            for place, n in positions:
                got = (np.asarray(low_all[i][n - 1]) if control
                       else logits.get((place, n)))
                if place == i and got is not None:
                    want = np.asarray(rows_all[i][n - 1])
                    out[name].add((float(np.abs(got - want).max()
                                         / want.std()),
                                   float(margin[n - 1]), rec["index"], n))
    return {name: sorted(rows) for name, rows in out.items()}


def _third(values: list) -> float:
    """The third largest: one or two readings of a run may be choices that
    rounding flipped beyond the margin, and are not held against it."""
    return sorted(values)[-3] if len(values) >= 3 else NOTHING_COMPARED


def reference_checks(spec, ref, sample, control: str, logits: dict):
    """`correct`, once the program's state is freed: :func:`compare`'s
    readings, each kind reduced to the numbers that have limits.
      decode_logit_gap, prefill_logit_gap   the largest reading
          (``serve_child``'s two numbers, the second at the last few tokens
          of each prompt). One flipped choice fills them, so
          their limits are wide: they catch what is wrong at every token;
      decode_logit_gap_3rd, tail_logit_gap_3rd   the third largest, with
          limits just above what bfloat16 gives: what a fault that hits one
          token in twenty (an expert left out) moves. The first is the one
          that reads the DECODE program (absorbed attention, the grouped
          product's small tiles): the engine hands out logits through its
          chunk program alone.
    A position whose own top-k choice the reference decides by less than
    ``limits.choice_margin`` in some expert layer (``choice_margin`` of the
    reference) is NOT compared: bfloat16 activations decide such a choice
    either way, and the other choice is another function of that token.
    ``undecided_share`` bounds how much of what was served the comparison
    may leave out that way. It is a property of the reference on the served
    tokens, so no fault in the program's arithmetic moves it: it holds the
    comparison to its coverage, and a check left with nothing to compare
    reads ``NOTHING_COMPARED`` and fails."""
    limits = spec["limits"]
    tau = limits["choice_margin"]
    read = compare(spec, ref, sample, control, logits)
    decided = {name: sorted(r[0] for r in rows if r[1] >= tau)
               for name, rows in read.items()}
    decode, prefill, tail = (decided[k] for k in ("decode", "prefill", "tail"))
    checks = [
        {"name": "decode_logit_gap",
         "value": max(decode, default=NOTHING_COMPARED),
         "tokens": len(read["decode"]), "requests": len(sample),
         "by_margin": {str(t): max((r[0] for r in read["decode"]
                                    if r[1] >= t), default=0.0)
                       for t in (0.0, tau / 2, tau, 2 * tau)},
         "widest": read["decode"][-4:]},
        {"name": "decode_logit_gap_3rd", "value": _third(decode),
         "positions": len(decode), "not_first": sum(g > 0 for g in decode),
         "largest": decode[-6:]},
        {"name": "undecided_share",
         "value": 1.0 - len(decode) / max(1, len(read["decode"]))},
        {"name": "prefill_logit_gap",
         "value": max(prefill, default=NOTHING_COMPARED),
         "positions": len(prefill)},
        {"name": "tail_logit_gap_3rd", "value": _third(tail),
         "positions": len(tail), "median": tail[len(tail) // 2] if tail
         else NOTHING_COMPARED, "largest": tail[-6:]},
    ]
    return [{**c, "limit": limits[c["name"]]} for c in checks]


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.serve_child_ref")
    p.add_argument("--spec", required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    ref = importlib.import_module(
        f"benchmark.reference.{spec['config']['reference']}")
    control = spec["control"]
    if control not in ref.CONTROLS:
        raise SystemExit(f"unknown control {control!r}")

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.monotonic(), name))
        if name.endswith("backend_compile_duration") else None)

    device, pcfg, gateway = build(spec, ref)
    vocab = pcfg.vocab_size          # the rows held: ids come from the slice
    warm_up(gateway, spec, vocab)
    replica = gateway.pool.ready_replicas()[0]
    gateway_deadline = gateway.admission.deadline_s

    begin = time.monotonic()
    t0 = begin + float(spec["traffic"].get("ramp_s", 0.0))
    t0_wall = time.time() + (t0 - begin)
    occupancy, waits, stop = [], [0.0], threading.Event()

    def sampler():
        while not stop.wait(0.25):
            if t0 <= time.monotonic() < t0 + spec["seconds"]:
                stats = gateway.stats()
                occupancy.append(stats["slot_occupancy"])
                waits.append(stats["estimated_wait_s"])

    def tracer():
        if stop.wait(t0 - begin + min(spec["trace_after_s"],
                                      spec["seconds"] / 4)):
            return
        jax.profiler.start_trace(spec["trace_dir"])
        stop.wait(min(spec["trace_seconds"], spec["seconds"] / 2))
        jax.profiler.stop_trace()

    side = [threading.Thread(target=sampler, name="occupancy")]
    if spec["trace"]:
        side.append(threading.Thread(target=tracer, name="tracer"))
    for t in side:
        t.start()
    window = drive(gateway, spec, vocab, begin, t0)
    stop.set()
    for t in side:
        t.join()
    in_window = sum(1 for t, _ in compiles if t0 <= t <= window["t_end"])
    summary = summarize(window, t0, spec["seconds"])
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    # (its two prefill logits are taken again below, with the other four)
    _, sample = sample_and_prefill(spec, replica.engine, window, control)
    logits = engine_logits(spec, replica.engine, sample, control)
    gateway.stop()
    replica.join(30)
    del replica, gateway
    gc.collect()
    t_check = time.monotonic()
    checks = reference_checks(spec, ref, sample, control, logits)
    late = [r["late_ms"] for r in summary["rows"]]
    out = {
        "device": device, "attempted": summary["attempted"],
        "failed": summary["failed"], "rows": summary["rows"],
        "e2e": {"serve_tokens_per_s": summary["serve_tokens_per_s"],
                "setup_s": t0_wall - spec["t_start"]},
        "occupancy": occupancy, "checks": checks,
        "notes": [{"compiles_in_window": in_window,
                   "backlog_mid": summary["backlog_mid"],
                   "backlog_end": summary["backlog_end"],
                   "generator_late_ms_max": max(late, default=0.0),
                   "admission_wait_s_max": max(waits),
                   "admission_deadline_s": gateway_deadline,
                   "check_seconds": time.monotonic() - t_check,
                   "child_setup_s": t0_wall - T_PROCESS,
                   "parameters_held": pcfg.param_count}],
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
