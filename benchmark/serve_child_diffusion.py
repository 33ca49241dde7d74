"""The serving child for a BLOCK-DIFFUSION configuration that names its
reference (``README.diffusion.md``; the configuration file's ``reference``: a
module of ``benchmark/reference/`` with ``weight``, ``logits_many`` and
``denoise_logits_many``). Started by the ``serve_gateway_diffusion`` driver
with a spec file; writes its answer as JSON, in the form ``serve_child``
writes.

What is general is imported: the warm-up, the traffic loop, the summary and
the sample are ``serve_child``'s, the reading
of a check left with nothing to compare is ``serve_child_ref``'s. What is
this family's: the published keys the program's preset must hold, the
weights built from the reference's leaves under the program's own leaf
names, token ids drawn from below the mask token's, and `correct`, which
REPLAYS what was served (``GatewayResult.unmask_steps``: in which pass each
token was unmasked) through the reference.
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
    published,
)

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
    "d_ff": "intermediate_size", "max_seq_len": "max_position_embeddings",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "moe_top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size",
    "n_routed_experts": "num_experts", "norm_topk_prob": "norm_topk_prob",
    "vocab_size": "vocab_size", "n_layers": "num_hidden_layers",
}
# published keys that say which kinds the program must run
KINDS = {"model_type": "sdar_moe", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "decoder_sparse_step": 1, "mlp_only_layers": [],
         "rope_scaling": None, "use_sliding_window": False,
         "sliding_window": None}
# program field -> the key of the file's `assumed` it must equal
ASSUMED = {"block_length": "block_length",
           "denoising_steps": "denoising_steps",
           "mask_token_id": "mask_token_id"}


def program_config(cfgf: dict):
    """The program's ``TransformerConfig`` for a configuration file: the
    preset it names must hold every published value and the generation the
    file assumes; then the layers held here are applied to it."""
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
    for field, key in ASSUMED.items():
        if getattr(base, field) != cfgf["assumed"][key]:
            raise SystemExit(
                f"config file assumes {key}={cfgf['assumed'][key]!r} but "
                f"the program's preset has {getattr(base, field)!r}")
    if ((base.attn_kind, base.norm_kind, base.ffn_kind, base.generation,
         base.rope_pairing) != ("heads_qk_norm", "pre", "softmax_experts",
                                "block_diffusion", "half")
            or cfgf["assumed"]["remasking"] != "low_confidence_static"):
        raise SystemExit(f"{cfgf['program_model']} is not of the kinds "
                         "this file publishes and assumes")
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=cfgf["num_hidden_layers"], dtype=dtype,
        param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers: each
    stack is filled in place, a layer's leaf at a time, so that at most one
    float32 leaf exists beside what is kept."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.models import transformer as tfm

    dt = jnp.dtype(pcfg.param_dtype)
    put = jax.jit(lambda stack, leaf, i: lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    shapes = tfm.param_shapes(pcfg)
    params = {name: ref.weight(cfgf, seed, ref.TOP, name).astype(dt)
              for name in ("embed", "ln_f", "lm_head")}
    params["layers"] = {}
    for name, shape in shapes["layers"].items():
        stack = jnp.zeros(shape, dt)
        for i in range(pcfg.n_layers):
            stack = put(stack, ref.weight(cfgf, seed, i, name), i)
        params["layers"][name] = stack
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


def _positions(spec, sample) -> tuple[list, list]:
    """Where the engine's own logits are taken, as ``(place in the sample,
    tokens fed)``, all at block-aligned ends (the chunk program hands out the
    logits of the last token fed, which sees its whole block only there):
    the last ``limits.prompt_positions`` such ends of every sampled prompt,
    and ``limits.tail_positions`` seeded ones in the last chunk of prompt
    plus answer."""
    import numpy as np

    from benchmark import traffic

    chunk, limits = spec["serving"]["prefill_len"], spec["limits"]
    b = spec["config"]["assumed"]["block_length"]
    rng = traffic.rng_for(spec["seed"], 0x7461696C)
    ends, tail = [], []
    for i, rec in enumerate(sample):
        whole = len(rec["prompt"]) // b * b
        ends += [(i, n) for n in range(
            max(b, whole - (limits["prompt_positions"] - 1) * b),
            whole + 1, b)]
        n_all = (len(rec["prompt"]) + len(rec["result"].tokens)) // b * b
        fed = np.arange((n_all - 1) // chunk * chunk + b, n_all + 1, b)
        tail += [(i, n) for n in sorted(rng.choice(
            fed, size=min(limits["tail_positions"], len(fed)),
            replace=False).tolist())]
    return ends, tail


def engine_logits(spec, engine, sample, control: str) -> dict:
    """The timed engine's own float32 logits, through its chunked-prefill
    program under the block-causal mask, at :func:`_positions` (prompt plus
    served answer fed as a prompt; after the first, the prefix cache resumes
    each from the row at the last chunk boundary). Nothing under a control,
    whose logits the reference supplies."""
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


_MADE: dict = {}


def compare(spec, ref, sample, control: str, logits: dict) -> dict:
    """The reference against what was served. Per reading ``(value, the
    reference's choice margin there, the request's index in the pool,
    where)``; a closed loop goes round its pool, so the same reading at the
    same place is ONE reading (a set).
      denoise   at each served position, in the pass that unmasked it (the
          replay of ``unmask_steps``), how far the served token's reference
          logit lies below the reference's best: reads the DECODE program,
          and the rows the prefill and the storing passes wrote;
      order     for every pass that had a choice: the reference's
          log-confidence of the best still-masked position the engine
          passed over, above that of the worst it unmasked (0 where the
          engine's choice is the reference's); its margin is the least of
          the pass's masked rows';
      chunk, tail   the engine's logits (:func:`engine_logits`) against
          ``logits_many``, the largest difference in units of the reference
          logits' standard deviation.
    ``control`` puts the reference with a fault in the program's place: the
    tokens, the choices and the logits it gives for the same trajectory."""
    import numpy as np

    cfgf, seed = spec["config"], spec["seed"]
    b = cfgf["assumed"]["block_length"]
    width = spec["serving"]["max_len"]
    blocks = -(-max(rec["max_new"] for rec in sample) // b) + 1
    requests = [(rec["prompt"], list(rec["result"].tokens),
                 list(rec["result"].unmask_steps)) for rec in sample]
    at_prompt, at_tail = _positions(spec, sample)
    seqs, wanted = [], []
    for i, (prompt, answer, _) in enumerate(requests):
        seq = np.zeros((width,), np.int32)
        seq[: len(prompt) + len(answer)] = prompt + answer
        seqs.append(seq)
        wanted.append(sorted({n - 1 for place, n in at_prompt + at_tail
                              if place == i}))

    def reference(fault: str):
        # the reference's two entry points on this sample, made once a
        # fault: a builder's list of controls shares the sound one
        key = (fault, tuple(id(rec) for rec in sample))
        if key not in _MADE:
            _MADE[key] = (
                ref.denoise_logits_many(cfgf, seed, requests, fault, width,
                                        blocks),
                ref.logits_many(cfgf, seed, seqs, fault, wanted))
        return _MADE[key]

    replay, (rows_all, margins) = reference("")
    faulted, (low_all, _) = reference(control) if control else (None,
                                                                (None, None))
    out = {"denoise": set(), "order": set(), "chunk": set(), "tail": set()}
    for i, rec in enumerate(sample):
        r, answer = replay[i], requests[i][1]
        rows = r["logits"]
        served = (faulted[i]["logits"].argmax(axis=-1) if control
                  else np.asarray(answer)[r["index"]])
        gaps = rows.max(axis=-1) - rows[np.arange(len(served)), served]
        margin = np.minimum(r["margin"], 99.0)
        out["denoise"] |= {(float(g), float(m), rec["index"], int(a))
                           for g, m, a in zip(gaps, margin, r["index"])}
        chosen = faulted[i]["would"] if control else r["chosen"]
        for n in range(r["pairs"]):
            took, left = chosen[n], r["masked"][n] & ~chosen[n]
            if not took.any() or not left.any():
                continue
            lc = r["log_conf"][n]
            out["order"].add((
                float(max(0.0, lc[left].max() - lc[took].min())),
                float(min(99.0, r["margin_pairs"][n][r["masked"][n]].min())),
                rec["index"], n))
        margin_seq = np.minimum(np.asarray(margins[i]), 99.0)
        for name, positions in (("chunk", at_prompt), ("tail", at_tail)):
            for place, n in positions:
                if place != i:
                    continue
                k = wanted[i].index(n - 1)
                got = (np.asarray(low_all[i][k]) if control
                       else logits.get((place, n)))
                if got is not None:
                    want = np.asarray(rows_all[i][k])
                    out[name].add((float(np.abs(got - want).max()
                                         / want.std()),
                                   float(margin_seq[n - 1]), rec["index"], n))
    return {name: sorted(rows) for name, rows in out.items()}


def _spread(values: list) -> list:
    """[how many, the largest, the third largest, the 90th percentile, the
    median, the mean] of a kind's readings at or above a margin: what a
    limit is chosen from."""
    v = sorted(values)
    if len(v) < 3:
        return [len(v)]
    return [len(v), v[-1], v[-3], v[int(0.9 * len(v))], v[len(v) // 2],
            sum(v) / len(v)]


def reference_checks(spec, ref, sample, control: str, logits: dict):
    """`correct`, once the program's state is freed: :func:`compare`'s
    readings, each kind reduced to the numbers that have limits.

    EVERY reading counts, whatever its margin. This family holds every
    expert in every layer, so a row's 8th and 9th router logits lie closer
    than the stated dtype resolves in SOME layer for nearly every row (97% at
    the margin 0.04 that ``README.named-reference.md`` leaves out): leaving
    those out leaves nothing, and an extreme statistic (a largest, a third
    largest) reads one flipped choice, which is as large as one expert left
    out. So each kind has a CENTRAL statistic, which flips that hit one row
    in ten cannot move and a fault that hits every row, or one row in four,
    does; and a largest-of-all with a wide limit for what is wrong
    everywhere.
      denoise_logit_gap        the largest; denoise_logit_gap_mean   the mean
          (seven served tokens in eight ARE the reference's best: sound
          reads 0.006-0.011 on the chip);
      unmask_order_gap_median  0 unless the engine unmasks another position
          than the reference's best in half the passes that had a choice;
      chunk_logit_gap          the largest over every block-aligned end;
      tail_logit_gap_median    the median over the tails' ends.
    A kind with fewer than three readings reads ``NOTHING_COMPARED`` and
    fails. The margins stay in the readings (``by_margin``) for whoever
    chooses limits."""
    limits = spec["limits"]
    read = compare(spec, ref, sample, control, logits)
    kinds = {name: sorted(r[0] for r in rows) for name, rows in read.items()}
    denoise, order, tail = kinds["denoise"], kinds["order"], kinds["tail"]
    ends = sorted(kinds["chunk"] + tail)

    def of(values, pick):
        return pick(values) if len(values) >= 3 else NOTHING_COMPARED

    checks = [
        {"name": "denoise_logit_gap", "value": of(denoise, max),
         "tokens": len(denoise), "requests": len(sample),
         "by_margin": {str(t): _spread([r[0] for r in read["denoise"]
                                        if r[1] >= t])
                       for t in (0.0, 0.005, 0.01, 0.02, 0.04)},
         "widest": read["denoise"][-4:]},
        {"name": "denoise_logit_gap_mean",
         "value": of(denoise, lambda v: sum(v) / len(v)),
         "not_first": sum(g > 0 for g in denoise)},
        {"name": "unmask_order_gap_median",
         "value": of(order, lambda v: v[len(v) // 2]), "passes": len(order),
         "not_best": sum(g > 0 for g in order), "largest": order[-6:]},
        {"name": "chunk_logit_gap", "value": of(ends, max),
         "positions": len(ends)},
        {"name": "tail_logit_gap_median",
         "value": of(tail, lambda v: v[len(v) // 2]), "positions": len(tail),
         "spread": _spread(tail)},
    ]
    return [{**c, "limit": limits[c["name"]]} for c in checks]


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.serve_child_diffusion")
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
    for control in controls:
        if control not in ref.CONTROLS:
            raise SystemExit(f"unknown control {control!r}")

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.monotonic(), name))
        if name.endswith("backend_compile_duration") else None)

    device, pcfg, gateway = build(spec, ref)
    # ids come from below the mask token's: no prompt holds it
    vocab = spec["config"]["assumed"]["mask_token_id"]
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
    gateway.stop()
    replica.join(30)
    del replica, gateway
    gc.collect()
    t_check = time.monotonic()
    checks = reference_checks(spec, ref, sample, controls[0], logits)
    others = {c: [{k: ch[k] for k in ("name", "value", "limit")}
                  for ch in reference_checks(spec, ref, sample, c, logits)]
              for c in controls[1:]}
    # a builder's run (CONTROL set) also notes every reading beside its
    # margin: what limits and margins are chosen from
    readings = {c or "sound": {
        kind: [[round(r[0], 5), round(r[1], 5)] for r in rows]
        for kind, rows in compare(spec, ref, sample, c, logits).items()}
        for c in (controls if spec["control"] else [])}
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
                   **({"other_controls": others} if others else {}),
                   **({"readings": readings} if readings else {})}],
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
