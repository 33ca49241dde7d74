"""The serving child: the one process that holds the chip, with the program's
``Gateway`` and one ``InferenceEngine`` replica in it. Started by the
``serve_gateway`` driver with a spec file; writes its answer as JSON.

Set-up: seeded float32 weights made on the device (the benchmark's), the
gateway built with the configuration's serving sizes, every program the
cell's traffic uses warmed by two waves of requests. Window: requests from
``benchmark.traffic`` through ``Gateway.submit`` — a closed loop of client
threads, or an open loop at due times. After the window has closed and the
program's memory peak has been read: `correct` (see
:func:`reference_checks`).
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import threading
import time

T_PROCESS = time.time()


def build(spec: dict):
    """(device dict, program config, gateway); set-up's heavy half."""
    import jax

    from benchmark import program
    from benchmark.reference import gpt2
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
    pcfg = program.program_config(cfgf, spec["rehearse"])
    # the engine gets the only reference to the weights: the reference
    # makes its own from the seed once the engine is freed
    weights = [jax.block_until_ready(gpt2.init_params(cfgf, spec["seed"]))]

    def engine_factory():
        return InferenceEngine(
            weights.pop(), pcfg, slots=serving["slots"], max_len=serving["max_len"],
            prefill_len=serving["prefill_len"],
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


def greedy(max_new: int):
    from dlrover_tpu.serving.engine import SamplingParams

    return SamplingParams(temperature=0.0, max_new_tokens=max_new,
                          eos_id=None)


def warm_up(gateway, spec: dict, vocab: int) -> None:
    """Every shape the window will use, and an admission estimate that has
    forgotten the compile. One request first: a prefill chunk whole and one
    partial, the install, and decode blocks of 8, 4, 2 and 1 steps (a block
    shrinks to the smallest remaining budget, so 15 new tokens walk the
    ladder). In a run that compiles it takes a minute, and the gateway's
    moving average of request times would refuse the window's traffic (429)
    for as long as it remembers that: short requests, one at a time, until
    the average is under two seconds. Then a full wave on every slot."""
    from benchmark import traffic
    from dlrover_tpu.gateway.server import AdmissionError

    slots, chunk = spec["serving"]["slots"], spec["serving"]["prefill_len"]
    block = spec["serving"]["decode_block"]
    rng = traffic.rng_for(spec["seed"], 0x7761726D)

    def submit(plen: int, new: int):
        while True:
            try:
                return gateway.submit(rng.integers(0, vocab, plen).tolist(),
                                      greedy(new))
            except AdmissionError:  # set-up may wait for room; the window may not
                time.sleep(0.2)

    submit(chunk + 6, 2 * block - 1).result(timeout=900)
    for _ in range(32):
        if gateway.stats()["ewma_request_s"] < 2.0:
            break
        submit(8, 2).result(timeout=900)
    for f in [submit(2 * chunk + 2, block + 1) for _ in range(slots)]:
        f.result(timeout=900)


def drive(gateway, spec: dict, vocab: int, begin: float, t0: float) -> dict:
    """The ramp, then the measured window: load starts at ``begin`` and the
    window at ``t0`` (monotonic clock), so the window opens on a system in
    its steady state, not an empty one. Returns the records of every
    request offered."""
    from benchmark import traffic
    from dlrover_tpu.gateway.server import AdmissionError

    mix, seconds = spec["traffic"], spec["seconds"]
    reqs = traffic.requests(mix, spec["seed"], seconds + (t0 - begin))
    records, lock = [], threading.Lock()
    t_end = t0 + seconds

    def offer(req, due_mono):
        prompt = traffic.prompt_ids(req, vocab)
        rec = {"index": req.index, "prompt": prompt, "due": due_mono,
               "max_new": req.max_new_tokens, "sent": time.monotonic()}
        with lock:
            records.append(rec)
        try:
            rec["future"] = gateway.submit(prompt, greedy(req.max_new_tokens))
        except AdmissionError as e:  # a 429: counts as failed
            rec["error"] = repr(e)
            time.sleep(0.05)
        return rec

    def finish(rec):
        if "future" not in rec:
            return
        try:
            rec["result"] = rec.pop("future").result(timeout=600)
        except Exception as e:  # noqa: BLE001 - a failed request is a count
            rec.pop("future", None)
            rec["error"] = repr(e)

    if mix["arrivals"]["kind"] == "closed":
        it = itertools.cycle(reqs)  # clients go round the pool

        def client():
            while time.monotonic() < t_end:
                with lock:
                    req = next(it)
                finish(offer(req, time.monotonic()))

        n = int(mix["arrivals"]["clients_per_slot"]) * spec["serving"]["slots"]
        threads = [threading.Thread(target=client, name=f"client-{i}")
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    else:
        for req in reqs:
            due = begin + req.due_s
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            offer(req, due)
        for rec in list(records):  # drain: every request gets its tail
            finish(rec)
    return {"records": records, "t_end": t_end}


def summarize(window: dict, t0: float, seconds: float) -> dict:
    """Per-request times (ms) and the end-to-end numbers of the window.
    ``serve_tokens_per_s`` counts every output token delivered inside the
    window, whichever request it belongs to: all the work of the window over
    all its time. Tails are over the requests due inside the window."""
    rows, failed, tokens_in_window = [], 0, 0
    for rec in window["records"]:
        res = rec.get("result")
        if res is None or not res.token_times:
            failed += 1
            continue
        times = res.token_times
        tokens_in_window += sum(1 for t in times if t0 <= t <= window["t_end"])
        if not t0 <= rec["due"] <= window["t_end"]:
            continue
        rows.append({
            "index": rec["index"], "prompt_tokens": len(rec["prompt"]),
            "output_tokens": len(res.tokens),
            "completed_in_window": times[-1] <= window["t_end"],
            "late_ms": 1e3 * (rec["sent"] - rec["due"]),
            "ttft_ms": 1e3 * (times[0] - rec["due"]),
            "queue_ms": 1e3 * res.queue_s, "prefill_ms": 1e3 * res.prefill_s,
            "decode_ms": 1e3 * res.decode_s,
            "gaps_ms": [1e3 * (b - a) for a, b in zip(times, times[1:])],
        })
    def backlog(at: float) -> int:
        """Requests due by ``at`` and not finished by then."""
        return sum(1 for rec in window["records"] if rec["due"] <= at and (
            rec.get("result") is None
            or rec["result"].token_times[-1] > at))

    return {"rows": rows, "attempted": len(window["records"]),
            "failed": failed,
            "backlog_mid": backlog(t0 + seconds / 2),
            "backlog_end": backlog(t0 + seconds),
            "serve_tokens_per_s": tokens_in_window / seconds}


def sample_and_prefill(spec, engine, window, control: str):
    """After the window: a seeded sample of the requests it finished, the
    longest among them; and for the first two of the sample the engine's own
    float32 logits of the last prompt token, through its chunked-prefill
    program on the timed engine object (the one place the engine hands out
    logits today)."""
    import numpy as np

    from benchmark import traffic

    done = [r for r in window["records"]
            if r.get("result") is not None and r["result"].tokens]
    if not done:
        raise SystemExit("the window finished no request")
    done.sort(key=lambda r: r["index"])
    longest = max(done,
                  key=lambda r: len(r["prompt"]) + len(r["result"].tokens))
    rng = traffic.rng_for(spec["seed"], 0x73616D70)
    picks = [done[i] for i in rng.choice(
        len(done), size=min(spec["sample"] - 1, len(done)), replace=False)]
    sample = [longest] + [r for r in picks if r is not longest]
    prefill = {}
    if not control:
        for rec in sample[:2]:
            run = engine.prefill_begin(rec["prompt"])
            while not engine.prefill_step(run):
                pass
            prefill[rec["index"]] = np.asarray(
                run.last, np.float32).reshape(-1)
    return prefill, sample


def reference_checks(spec, params, prefill, sample, control: str):
    """`correct`, once the program's state is freed. The reference runs once
    over each sampled prompt plus its served answer:
      decode_logit_gap   at each served position, how far the served token's
          reference logit lies below the reference's best; the widest gap;
      prefill_logit_gap  the engine's logits of the last prompt token
          against the reference's, the largest difference in units of the
          reference logits' standard deviation.
    ``control`` puts the reference at a lower precision in the program's
    place: the token that precision puts first, at the same positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import gpt2

    cfgf, limits = spec["config"], spec["limits"]
    width = spec["serving"]["max_len"]
    eps = cfgf.get("layer_norm_epsilon", 1e-5)
    ref = jax.jit(lambda p, t: gpt2.logits(p, t, eps)[0])
    low = (jax.jit(lambda p, t: gpt2.logits(p, t, eps, control)[0])
           if control else None)
    prefill_gap, decode_gap, n_tokens = 0.0, 0.0, 0
    for rec in sample:
        prompt, answer = rec["prompt"], list(rec["result"].tokens)
        seq = np.zeros((1, width), np.int32)
        seq[0, : len(prompt) + len(answer)] = prompt + answer
        lg = ref(params, jnp.asarray(seq))
        at = np.arange(len(prompt) - 1, len(prompt) + len(answer) - 1)
        rows = np.asarray(lg[at])                     # [answer, vocab]
        served = np.asarray(answer)
        if low is not None:
            low_rows = np.asarray(low(params, jnp.asarray(seq))[at])
            served = low_rows.argmax(axis=-1)
            if rec in sample[:2]:
                prefill[rec["index"]] = low_rows[0]
        gaps = rows.max(axis=-1) - rows[np.arange(len(at)), served]
        decode_gap = max(decode_gap, float(gaps.max()))
        n_tokens += len(at)
        if rec["index"] in prefill:
            diff = np.abs(prefill[rec["index"]] - rows[0]).max()
            prefill_gap = max(prefill_gap, float(diff / rows[0].std()))
    checks = [{"name": "decode_logit_gap", "value": decode_gap,
               "limit": limits["decode_logit_gap"], "tokens": n_tokens,
               "requests": len(sample)}]
    if prefill:
        checks.append({"name": "prefill_logit_gap", "value": prefill_gap,
                       "limit": limits["prefill_logit_gap"]})
    return checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.serve_child")
    p.add_argument("--spec", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--control", default="", choices=("", "fp8"))
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.monotonic(), name))
        if name.endswith("backend_compile_duration") else None)

    device, pcfg, gateway = build(spec)
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
                # how near the window came to a 429: admission refuses
                # where this estimate passes its deadline
                waits.append(stats["estimated_wait_s"])

    def tracer():
        # a slice of the window, a few seconds in
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

    prefill, sample = sample_and_prefill(spec, replica.engine, window,
                                         args.control)
    gateway.stop()
    replica.join(30)
    del replica, gateway
    gc.collect()
    t_check = time.monotonic()
    from benchmark.reference import gpt2

    params = gpt2.init_params(spec["config"], spec["seed"])
    checks = reference_checks(spec, params, prefill, sample, args.control)
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
                   "child_setup_s": t0_wall - T_PROCESS}],
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
