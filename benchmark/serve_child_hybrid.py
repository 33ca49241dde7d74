"""The serving child for a HYBRID-CACHE configuration (block-sparse attention
over a compressed-key cache beside linear-attention layers whose cache is a
state; ``benchmark/reference/minicpm_sala.py``). Started by the
``serve_gateway_hybrid`` driver with a spec file; writes its answer as JSON,
in the form ``serve_child`` writes. ``README.hybrid.md`` beside this file.

The traffic loop, the warm-up, the summary and the sample are
``serve_child``'s; where the engine's own logits are taken is
``serve_child_ref``'s. What is this family's: every published key and every
assumed size of the file against the program's preset (or the run stops),
the program's stacks built from the reference's leaves a kind at a time, and
`correct` (:func:`reference_checks`).
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
from benchmark.serve_child_ref import (  # noqa: E402
    NOTHING_COMPARED,
    _positions,
    _third,
    engine_logits,
    published,
)

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "sparse_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "max_seq_len": "max_position_embeddings", "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps", "n_layers": "num_hidden_layers",
    "n_kv_heads": "lightning_nkv", "embed_scale": "scale_emb",
}
# published keys that say which kinds the program must run
KINDS = {"model_type": "minicpm_sala", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "attn_use_rope": False, "lightning_use_rope": True,
         "lightning_scale": "1/sqrt(d)", "qk_norm": True,
         "use_output_gate": True, "use_output_norm": True,
         "attn_use_output_gate": True}
# the published names of the two mixers -> the program's
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
# program field -> the key of ``assumed.sparse_config`` it must equal
SPARSE = {"sparse_kernel": "kernel_size", "sparse_stride": "kernel_stride",
          "sparse_block": "block_size", "sparse_topk": "topk",
          "sparse_init_blocks": "init_blocks",
          "sparse_window": "window_size", "sparse_dense_len": "dense_len"}


def program_config(cfgf: dict):
    """The program's ``TransformerConfig`` for a configuration file: the
    preset it names must hold every published value and every assumed
    size, then the depth the file holds is applied to it."""
    import dataclasses
    import math

    from dlrover_tpu.models import transformer as tfm

    if cfgf["program_model"] not in tfm.CONFIGS:
        raise SystemExit(f"the program has no preset "
                         f"{cfgf['program_model']!r}: it cannot run this "
                         "configuration")
    base = tfm.CONFIGS[cfgf["program_model"]]

    def same(what, mine, theirs):
        if mine != theirs:
            raise SystemExit(f"config file {what}={theirs!r} but the "
                             f"program's {cfgf['program_model']} has "
                             f"{mine!r}")

    for field, key in PUBLISHED.items():
        same(key, getattr(base, field), published(cfgf, key))
    for key, value in KINDS.items():
        same(key, value, cfgf[key])
    for field, key in SPARSE.items():
        same(f"assumed.sparse_config.{key}", getattr(base, field),
             cfgf["assumed"]["sparse_config"][key])
    same("mixer_types", base.mixer_types,
         tuple(MIXERS[m] for m in published(cfgf, "mixer_types")))
    same("lightning_nh / lightning_head_dim", (base.n_heads, base.head_dim),
         (cfgf["lightning_nh"], cfgf["lightning_head_dim"]))
    same("scale_depth / sqrt(num_hidden_layers)",
         round(base.residual_scale, 9),
         round(cfgf["scale_depth"]
               / math.sqrt(published(cfgf, "num_hidden_layers")), 9))
    same("dim_model_base / hidden_size", base.logit_scale,
         cfgf["dim_model_base"] / cfgf["hidden_size"])
    if base.attn_kind != "mixers":
        raise SystemExit(f"{cfgf['program_model']} is not of the kinds this "
                         "file publishes")
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=cfgf["num_hidden_layers"],
        mixer_types=tuple(MIXERS[m] for m in cfgf["mixer_types"]),
        dtype=dtype, param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers under
    the program's own leaf names: each kind's stack is filled in place, a
    layer's leaf at a time, so that at most one float32 leaf exists beside
    what is kept."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.models import hybrid

    dt = jnp.dtype(pcfg.param_dtype)
    put = jax.jit(lambda stack, leaf, i: lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    shapes = hybrid.param_shapes(pcfg)
    params = {name: ref.weight(cfgf, seed, ref.TOP, name).astype(dt)
              for name in ("embed", "ln_f", "lm_head")}
    for kind in hybrid.KINDS:
        layers = [i for i, m in enumerate(pcfg.mixer_types) if m == kind]
        params[f"{kind}_layers"] = {}
        for name, shape in shapes[f"{kind}_layers"].items():
            stack = jnp.zeros(shape, dt)
            for at, layer in enumerate(layers):
                stack = put(stack, ref.weight(cfgf, seed, layer, name), at)
            params[f"{kind}_layers"][name] = stack
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

    # a request of this traffic lives half a minute: the deployment's
    # admission deadline is the file's, not the gateway's default
    gateway = Gateway(engine_factory, replicas=1,
                      prefill_len=serving["prefill_len"],
                      admission_deadline_s=serving["admission_deadline_s"])
    deadline = time.monotonic() + 900
    while not gateway.pool.ready_replicas():
        if time.monotonic() > deadline:
            raise SystemExit("the replica never became ready")
        time.sleep(0.05)
    return device, pcfg, gateway


def compare(spec, ref, sample, control: str, logits: dict,
            memo: dict | None = None) -> dict:
    """The reference run once over each sampled prompt plus its served
    answer (all padded to one width: a multiple of the sparse block and of
    1024 that holds the longest), against what the engine served and the
    logits it gave. Readings ``(value, the request's index in the pool,
    tokens fed)``, a SET a kind (a closed loop goes round its pool, so a
    sample may hold one request several times):
      decode   at each served position, how far the served token's
          reference logit lies below the reference's best;
      prefill, tail   the engine's own logits (``serve_child_ref.
          engine_logits``: the last tokens of each prompt; seeded
          positions of the last chunk of prompt plus answer, resumed
          through the prefix cache from the row AND THE STATE at the chunk
          boundary) against the reference's, the largest difference in
          units of the reference logits' standard deviation.
    ``control`` puts the reference with a fault in the program's place: the
    token that puts first, and its logits, at the same positions. ``memo``
    keeps the sound reference's rows of a sample for the next control."""
    import numpy as np

    cfgf = spec["config"]
    lengths = [len(r["prompt"]) + len(r["result"].tokens) for r in sample]
    width = min(-(-max(lengths) // 1024) * 1024, spec["serving"]["max_len"])
    at_prompt, at_tail = _positions(spec, sample)
    seqs, wanted, prompts = [], [], []
    for i, rec in enumerate(sample):
        seq = np.zeros((width,), np.int32)
        tokens = rec["prompt"] + list(rec["result"].tokens)
        seq[: len(tokens)] = tokens
        seqs.append(seq)
        served = range(len(rec["prompt"]) - 1, len(tokens) - 1)
        wanted.append(sorted(set(served) | {
            n - 1 for place, n in at_prompt + at_tail if place == i}))
        prompts.append(len(rec["prompt"]))

    def run(fault):
        return [np.asarray(rows) for rows in ref.logits_many(
            cfgf, spec["seed"], seqs, fault, wanted, prompts)]

    memo = {} if memo is None else memo
    key = tuple(id(rec) for rec in sample)
    if key not in memo:
        memo[key] = run("")
    rows_all = memo[key]
    low_all = run(control) if control else None
    out = {"decode": set(), "prefill": set(), "tail": set()}
    for i, rec in enumerate(sample):
        row_of = {p: j for j, p in enumerate(wanted[i])}
        rows = rows_all[i]
        low = low_all[i] if control else None
        prompt, answer = rec["prompt"], list(rec["result"].tokens)
        for k, token in enumerate(answer):
            j = row_of[len(prompt) - 1 + k]
            if control:
                token = int(low[j].argmax())
            out["decode"].add((float(rows[j].max() - rows[j][token]),
                               rec["index"], len(prompt) + k))
        for name, positions in (("prefill", at_prompt), ("tail", at_tail)):
            for place, n in positions:
                if place != i:
                    continue
                j = row_of[n - 1]
                got = low[j] if control else logits.get((place, n))
                if got is not None:
                    out[name].add((float(np.abs(got - rows[j]).max()
                                         / rows[j].std()), rec["index"], n))
    return {name: sorted(rows) for name, rows in out.items()}


def reference_checks(spec, ref, sample, control: str, logits: dict,
                     memo: dict | None = None):
    """`correct`, once the program's state is freed: :func:`compare`'s
    readings, each kind reduced to the numbers that have limits. A model
    whose attention SELECTS has, like a routed one, positions where
    rounding decides a choice (the 64th block against the 65th), and the
    other choice is another function of that token: so each kind is read
    by its largest (a wide limit: what is wrong everywhere) and by a
    statistic that one or two flipped choices cannot fill (a tight one).
      decode_logit_gap, decode_logit_gap_mean   the largest and the mean
          of the served positions' gaps: they read the DECODE program (the
          gathered blocks, the state carried a token at a time);
      prefill_logit_gap    the largest at the prompts' ends;
      tail_logit_gap_3rd, tail_logit_gap_median   the third largest and
          the median over the tails: the chunk program resumed from a
          stored row and state.
    A kind with fewer than three readings reads ``NOTHING_COMPARED`` and
    fails."""
    limits = spec["limits"]
    read = compare(spec, ref, sample, control, logits, memo)
    kinds = {name: [r[0] for r in rows] for name, rows in read.items()}
    decode, prefill, tail = kinds["decode"], kinds["prefill"], kinds["tail"]

    def of(values, pick):
        return pick(values) if len(values) >= 3 else NOTHING_COMPARED

    checks = [
        {"name": "decode_logit_gap", "value": of(decode, max),
         "tokens": len(decode), "requests": len(sample),
         "not_first": sum(g > 0 for g in decode), "widest": read["decode"][-4:]},
        {"name": "decode_logit_gap_mean",
         "value": of(decode, lambda v: sum(v) / len(v))},
        {"name": "prefill_logit_gap", "value": of(prefill, max),
         "positions": len(prefill)},
        {"name": "tail_logit_gap_3rd", "value": _third(tail),
         "positions": len(tail), "largest": read["tail"][-4:]},
        {"name": "tail_logit_gap_median",
         "value": of(tail, lambda v: v[len(v) // 2])},
    ]
    return [{**c, "limit": limits[c["name"]]} for c in checks]


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.serve_child_hybrid")
    p.add_argument("--spec", required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    ref = importlib.import_module(
        f"benchmark.reference.{spec['config']['reference']}")
    # CONTROL=a puts fault a in the program's place; a builder's list
    # (CONTROL=sound,a,b) decides `correct` by its first entry ("sound":
    # the program itself) and puts the others' numbers, on the same
    # sample, into the notes
    controls = ["" if c == "sound" else c
                for c in spec["control"].split(",")]
    # (a builder's list may also hold the reference's READINGS: no faults,
    # what rounding alone gives)
    known = ref.CONTROLS + (ref.READINGS if len(controls) > 1 else ())
    for control in controls:
        if control not in known:
            raise SystemExit(f"unknown control {control!r}")

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.monotonic(), name))
        if name.endswith("backend_compile_duration") else None)

    device, pcfg, gateway = build(spec, ref)
    vocab = pcfg.vocab_size
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

    # (any control name: the sample alone, none of its own prefill logits)
    _, sample = sample_and_prefill(spec, replica.engine, window, "sample")
    logits = engine_logits(spec, replica.engine, sample, controls[0])
    engine = replica.engine
    cache_notes = {"cache_bytes_per_token": engine.cache_bytes_per_token,
                   "state_bytes_per_slot": engine.state_bytes_per_slot}
    gateway.stop()
    replica.join(30)
    del replica, gateway, engine
    gc.collect()
    t_check = time.monotonic()
    checks = reference_checks(spec, ref, sample, controls[0], logits)
    # the list's other faults are read on the sample's two SHORTEST (a
    # fault costs a reference pass), whose sound rows are computed once
    few = sorted(sample, key=lambda r: len(r["prompt"])
                 + len(r["result"].tokens))[:2]
    memo: dict = {}
    others = {c: [{k: ch[k] for k in ("name", "value", "limit")}
                  for ch in reference_checks(spec, ref, few, c, {}, memo)]
              for c in controls[1:]}
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
                   "parameters_held": pcfg.param_count,
                   "sampled_tokens": [len(r["prompt"])
                                      + len(r["result"].tokens)
                                      for r in sample],
                   **cache_notes,
                   **({"other_controls": others} if others else {})}],
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
