"""The one general traffic generator: a workload file's parameters plus
``--seed`` give the requests of a run. NumPy only; no JAX.

A mix fixes the SEQUENCE of request sizes and arrival gaps (drawn once from
the mix's own ``mix_seed``); the run's seed picks where in that cycle the run
starts and draws the token ids. So every seed offers the same work in
another order, and two runs of one seed are identical. (A free permutation
was tried first: on the chip two seeds' closed-loop rates differed by 10%
where two runs of one seed differed by 0.3%, because a window holds only
some tens of requests and their neighbourhoods decide how prefill and decode
interleave.)

Workload-file keys read here (``traffic`` object):
  prompt_tokens / output_tokens   {"dist": "lognormal", "median", "sigma",
                                   "min", "max"} or {"dist": "fixed", "value"}
  arrivals   {"kind": "closed", "clients_per_slot": n}
             {"kind": "poisson", "rate_per_s": r}
  ramp_s     seconds of load before the window opens (default 0)
  pool       how many distinct sizes the mix holds (default 512); a window
             should go round it at least once, so that every seed offers
             the same set
  mix_seed   seed of the set (default 0)
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # offset from the window's start; 0.0 in a closed loop
    prompt_tokens: int
    max_new_tokens: int
    token_seed: int       # the prompt's ids are drawn from this


def rng_for(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) & 0xFFFFFFFF for w in words] + [int(words[0]) >> 32]))


def _draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _gaps(arr: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    return rng.exponential(1.0 / float(arr["rate_per_s"]), n)


def requests(traffic: dict, seed: int, seconds: float) -> list[Request]:
    """The requests of one run. Open loop: those due inside the window.
    Closed loop: the pool once through, which clients cycle."""
    pool = int(traffic.get("pool", 512))
    mix = rng_for(int(traffic.get("mix_seed", 0)), 0x6D6978)
    prompts = _draw(traffic["prompt_tokens"], pool, mix)
    outputs = _draw(traffic["output_tokens"], pool, mix)
    arr = traffic["arrivals"]
    run = rng_for(seed, 0x72756E)
    start = int(run.integers(0, pool))
    if arr["kind"] == "closed":
        order = (start + np.arange(pool)) % pool
        dues = np.zeros(pool)
    else:
        n = max(1, math.ceil(float(arr["rate_per_s"]) * seconds * 1.5) + 8)
        gaps = _gaps(arr, pool, mix)
        dues = np.cumsum(gaps[(start + np.arange(n)) % pool])
        order = (start + np.arange(n)) % pool
        keep = dues < seconds
        dues, order = dues[keep], order[keep]
    token_seeds = run.integers(0, 2**31 - 1, len(order))
    return [Request(i, float(dues[i]), int(prompts[j]), int(outputs[j]),
                    int(token_seeds[i]))
            for i, j in enumerate(order)]


def prompt_ids(req: Request, vocab: int) -> list[int]:
    """The prompt's token ids, uniform over the vocabulary."""
    return rng_for(req.token_seed, 0x746F6B).integers(
        0, vocab, req.prompt_tokens).tolist()


def token_file(path: str, vocab: int, n_tokens: int, seed: int) -> None:
    """A flat uint32 token file for a training job, uniform ids from the
    seed (the packed format ``--data-file`` of the trainer example reads)."""
    rng_for(seed, 0x64617461).integers(
        0, vocab, n_tokens, dtype=np.uint32).tofile(path)
