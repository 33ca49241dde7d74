"""Disaggregated prefill/decode serving (ISSUE 12 tentpole).

The properties that make the split worth shipping:

- token identity: a request prefilled on the prefill pool and decoded
  on the decode pool emits bit-identical tokens to the unified path
  (same gateway-minted seed);
- paged KV: eviction (park) + readmission round-trips bit-identically
  under a seeded open-loop trace, and a long generation no longer
  blocks a short one behind a dense slot;
- the shard ring keeps prefix families on one gateway shard and moves
  ~1/N of the keyspace on membership change;
- the split autoscaler sizes the prefill pool by prompt backlog and
  the decode pool by occupancy — independently, with hysteresis.
"""

from __future__ import annotations

import time

import pytest

import jax

from dlrover_tpu.gateway import (
    DisaggAutoscaler,
    DisaggSignals,
    Gateway,
    PoolScaler,
    ShardRing,
)
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving import (
    InferenceEngine,
    PrefillEngine,
    SamplingParams,
)

CFG = tfm.CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _factory(params, *, kv_pages=0):
    def build():
        return InferenceEngine(
            params, CFG, slots=2, max_len=64, prefill_len=8,
            prefix_cache_entries=4, kv_pages=kv_pages,
        )
    return build


def _wait(cond, timeout=90.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ------------------------------------------------------------- shard ring


class TestShardRing:
    def test_prefix_family_colocates(self):
        ring = ShardRing(8, ["gw-0", "gw-1", "gw-2"])
        sys_prompt = list(range(100, 108))
        shards = {
            ring.shard_for(sys_prompt + [extra, extra + 1])
            for extra in range(20)
        }
        # every member of the prefix family lands on ONE shard
        assert len(shards) == 1

    def test_distribution_covers_all_shards(self):
        ring = ShardRing(8, [f"gw-{i}" for i in range(4)])
        hits = {}
        for base in range(200):
            s = ring.shard_for([base * 17 + j for j in range(8)])
            hits[s] = hits.get(s, 0) + 1
        assert len(hits) == 4          # nobody starved
        assert max(hits.values()) < 200 * 0.6  # nobody owns everything

    def test_membership_change_moves_bounded_fraction(self):
        shards = [f"gw-{i}" for i in range(4)]
        ring = ShardRing(8, shards)
        keys = [[base * 31 + j for j in range(8)] for base in range(300)]
        before = [ring.shard_for(k) for k in keys]
        ring.remove_shard("gw-2")
        after = [ring.shard_for(k) for k in keys]
        moved = sum(1 for b, a in zip(before, after) if b != a)
        # only gw-2's keys move (~1/4 of the space), nothing else
        assert all(b == "gw-2" for b, a in zip(before, after) if b != a)
        assert 0 < moved < 300 * 0.5
        # re-adding restores the original assignment exactly
        ring.add_shard("gw-2")
        assert [ring.shard_for(k) for k in keys] == before

    def test_short_prompts_and_empty_ring(self):
        ring = ShardRing(8)
        assert ring.shard_for([1, 2, 3]) is None
        ring.add_shard("gw-0")
        assert ring.shard_for([1, 2]) == "gw-0"
        assert ring.shards() == ["gw-0"]


# ----------------------------------------------------- split autoscaler


class TestDisaggAutoscaler:
    def _asc(self, signals, **kw):
        plans = []

        class _Recorder:
            def scale(self, plan):
                plans.append(plan)

        it = iter(signals)
        asc = DisaggAutoscaler(
            gateway=None, prefill_scaler=_Recorder(),
            decode_scaler=_Recorder(),
            min_prefill=1, max_prefill=4, min_decode=1, max_decode=4,
            down_ticks=2, signals_fn=lambda: next(it), **kw,
        )
        return asc, plans

    def test_prefill_backlog_scales_only_prefill(self):
        sig = DisaggSignals(prefill_backlog=10, prefill_live=1,
                            decode_queue=0, decode_occupancy=0.5,
                            decode_live=2, slots_per_replica=2)
        asc, plans = self._asc([sig])
        asc.tick()
        assert asc.prefill_policy.target == 2
        assert asc.decode_policy.target == 2      # untouched
        # both scalers saw the SAME plan carrying both groups
        assert plans[-1].replica_resources == {"prefill": 2,
                                               "decode": 2}

    def test_decode_occupancy_scales_only_decode(self):
        sig = DisaggSignals(prefill_backlog=0, prefill_live=2,
                            decode_queue=0, decode_occupancy=0.95,
                            decode_live=2, slots_per_replica=2)
        asc, _ = self._asc([sig])
        asc.tick()
        assert asc.decode_policy.target == 3
        # empty prefill queue is COLD for prefill, but hysteresis holds
        # the first tick
        assert asc.prefill_policy.target == 2

    def test_down_needs_streak_per_pool(self):
        cold = DisaggSignals(prefill_backlog=0, prefill_live=3,
                             decode_queue=0, decode_occupancy=0.1,
                             decode_live=3, slots_per_replica=2)
        asc, _ = self._asc([cold, cold, cold])
        asc.tick()
        assert (asc.prefill_policy.target,
                asc.decode_policy.target) == (3, 3)
        asc.tick()   # streak of 2 reached for both pools
        assert (asc.prefill_policy.target,
                asc.decode_policy.target) == (2, 2)

    def test_mixed_load_diverges_pools(self):
        """Prefill-bound then decode-bound load drives the two targets
        in opposite directions — the thrash a single shared signal
        could never avoid."""
        prefill_bound = DisaggSignals(
            prefill_backlog=12, prefill_live=1, decode_queue=0,
            decode_occupancy=0.1, decode_live=2, slots_per_replica=2)
        asc, _ = self._asc([prefill_bound] * 3)
        for _ in range(3):
            asc.tick()
        assert asc.prefill_policy.target > 2
        assert asc.decode_policy.target <= 2

    def test_restore_emits_plan(self):
        steady = DisaggSignals(prefill_backlog=1, prefill_live=0,
                               decode_queue=0, decode_occupancy=0.5,
                               decode_live=2, slots_per_replica=2)
        asc, plans = self._asc([steady])
        asc.prefill_policy.target = 1
        asc.decode_policy.target = 2
        asc.tick()
        assert plans and plans[-1].replica_resources["prefill"] == 1


# ------------------------------------------------------ prefill engine


@pytest.mark.timeout(300)
def test_prefill_engine_chunks_and_bundles(params):
    """One chunk per step (drain/kill stay responsive mid-prompt);
    bundles are page-granular, covering exactly ceil(prompt/page)."""
    eng = PrefillEngine(_factory(params)())
    long_prompt = list(range(19))            # 3 chunks at P=8
    rid = eng.submit(long_prompt)
    steps = 0
    while eng.outstanding:
        eng.step()
        steps += 1
        assert steps < 20
    assert steps >= 3                        # chunked, not monolithic
    [res] = eng.poll_results()
    assert res.id == rid and res.chunks == 3
    assert res.bundle.pos == 19
    assert res.bundle.stacks["k"].shape[1] == 3        # ceil(19/8) pages shipped
    with pytest.raises(ValueError):
        eng.submit([])


# ------------------------------------------------- disagg token identity


@pytest.mark.timeout(300)
def test_disagg_tokens_identical_to_unified(params):
    """ISSUE 12 acceptance: prefill on the prefill pool + decode on the
    decode pool == the unified path, bit for bit, for greedy AND
    sampled requests (the gateway mints the same seed either way)."""
    prompts = [[5, 9, 2],
               list(range(40, 56)) + [3],    # 2 aligned chunks + tail
               [7, 7, 7, 7, 1]]
    sps = [SamplingParams(temperature=0.9, top_p=0.95,
                          max_new_tokens=8),
           SamplingParams(temperature=0.0, max_new_tokens=6),
           SamplingParams(temperature=0.7, top_k=20,
                          max_new_tokens=5)]

    uni = Gateway(_factory(params), replicas=1, prefill_len=8, seed=42)
    assert _wait(lambda: len(uni.pool.ready_replicas()) == 1)
    want = [uni.generate(p, s, timeout=120).tokens
            for p, s in zip(prompts, sps)]
    uni.stop()

    dis = Gateway(_factory(params, kv_pages=16), replicas=1,
                  prefill_len=8, prefill_replicas=1, seed=42)
    assert _wait(lambda: len(dis.pool.ready_replicas()) == 1
                 and len(dis.prefill_pool.ready_replicas()) == 1)
    try:
        got = [dis.generate(p, s, timeout=120).tokens
               for p, s in zip(prompts, sps)]
        assert got == want
        stats = dis.stats()
        assert stats["disaggregated"] and stats["prefill_ready"] == 1
    finally:
        dis.stop()


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): the ScalePlan resize path is already
# pinned per-pool by test_gateway's scaleplan test + the pure
# DisaggAutoscaler tests above; this e2e re-proves it with live
# engine builds. `pytest tests/` still runs it.
@pytest.mark.slow
def test_disagg_pools_scale_independently(params):
    """The ScalePlan path resizes each pool by its own group key."""
    gw = Gateway(_factory(params), replicas=1, prefill_len=8,
                 prefill_replicas=1, health_interval_s=0.1)
    assert _wait(lambda: len(gw.pool.ready_replicas()) == 1
                 and len(gw.prefill_pool.ready_replicas()) == 1)
    try:
        from dlrover_tpu.cluster.crd import ScalePlan

        prefill_scaler = PoolScaler(gw.prefill_pool, group="prefill")
        decode_scaler = PoolScaler(gw.pool, group="decode")
        plan = ScalePlan(replica_resources={"prefill": 2, "decode": 1},
                         reason="test")
        prefill_scaler.scale(plan)
        decode_scaler.scale(plan)
        assert _wait(
            lambda: len(gw.prefill_pool.ready_replicas()) == 2)
        assert len(gw.pool.ready_replicas()) == 1
        # and the grown prefill tier still serves identical results
        res = gw.generate([5, 9, 2], SamplingParams(
            temperature=0.0, max_new_tokens=4), timeout=120)
        assert len(res.tokens) == 4
    finally:
        gw.stop()


# --------------------------------------------- paged eviction round trip


@pytest.mark.timeout(300)
def test_paged_eviction_readmission_seeded_trace(params):
    """Seeded open-loop-shaped trace on a page-pooled engine: parks
    and resumes MUST happen, every request completes, and every token
    stream is bit-identical to the dense (no-paging) engine."""
    import random

    rng = random.Random(7)
    reqs = []
    for i in range(8):
        plen = rng.randint(1, 12)
        reqs.append((
            [rng.randrange(CFG.vocab_size) for _ in range(plen)],
            SamplingParams(
                temperature=rng.choice([0.0, 0.8]),
                max_new_tokens=rng.randint(2, 20),
                seed=1000 + i),
        ))

    def run(kv_pages):
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8, kv_pages=kv_pages)
        order = []
        ids = [eng.submit(p, sp) for p, sp in reqs]
        out = {}
        for r in eng.run():
            out[r.id] = r.tokens
            order.append(r.id)
        return eng, [out[i] for i in ids], order

    dense_eng, dense, _ = run(0)
    paged_eng, paged, order = run(24)
    assert paged == dense                      # bit-identical streams
    assert paged_eng.kv_parked_total >= 1      # eviction actually ran
    assert paged_eng.free_pages == 24          # every page returned
    assert dense_eng.kv_parked_total == 0


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): the park/resume identity + ledger
# accounting stay covered in-tier by the seeded round-trip test
# above; this adds the completion-ORDER claim. `pytest tests/`
# still runs it.
@pytest.mark.slow
def test_paged_long_generation_does_not_block_short(params):
    """The ROADMAP complaint: one long generation pinning a dense slot
    starves admission. With paging, the short request is parked IN and
    finishes first; the long one resumes and still matches dense."""
    eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                          prefill_len=8, kv_pages=16)
    long_id = eng.submit([5, 9, 2], SamplingParams(
        temperature=0.0, max_new_tokens=30))
    short_id = eng.submit([7, 7], SamplingParams(
        temperature=0.0, max_new_tokens=4))
    results = eng.run()
    assert [r.id for r in results] == [short_id, long_id]
    assert eng.kv_parked_total >= 1

    dense = InferenceEngine(params, CFG, slots=1, max_len=64,
                            prefill_len=8)
    d_long = dense.submit([5, 9, 2], SamplingParams(
        temperature=0.0, max_new_tokens=30))
    d_short = dense.submit([7, 7], SamplingParams(
        temperature=0.0, max_new_tokens=4))
    dense_out = {r.id: r.tokens for r in dense.run()}
    paged_out = {r.id: r.tokens for r in results}
    assert paged_out[long_id] == dense_out[d_long]
    assert paged_out[short_id] == dense_out[d_short]

    # page ledger at submit time: a request that cannot ever fit the
    # pool is rejected up front, not wedged in the queue
    tiny = InferenceEngine(params, CFG, slots=1, max_len=64,
                           prefill_len=8, kv_pages=2)
    with pytest.raises(ValueError, match="pages"):
        tiny.submit([1] * 10, SamplingParams(max_new_tokens=20))


# ----------------------------------------------- causal request traces (§27)


_TRACE_DRIVER = """
import json, os, pickle, sys

role, work = sys.argv[1], sys.argv[2]
import jax  # noqa: E402
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving import InferenceEngine, SamplingParams
from dlrover_tpu.serving.prefill import PrefillEngine

cfg = tfm.CONFIGS["tiny"]
params = tfm.init_params(cfg, jax.random.PRNGKey(0))
engine = InferenceEngine(params, cfg, slots=2, max_len=64,
                         prefill_len=8, kv_pages=16)
with open(os.path.join(work, "req.json")) as f:
    spec = json.load(f)
if role == "prefill":
    pe = PrefillEngine(engine)
    pe.submit(spec["prompt"], sctx=spec["sctx"])
    while pe.step():
        pass
    [res] = pe.poll_results()
    with open(os.path.join(work, "bundle.pkl"), "wb") as f:
        pickle.dump(res.bundle, f)
else:
    with open(os.path.join(work, "bundle.pkl"), "rb") as f:
        bundle = pickle.load(f)
    engine.submit_prefilled(
        spec["prompt"],
        SamplingParams(temperature=0.0, max_new_tokens=4),
        bundle=bundle)
    done = []
    while not done:
        engine.step()
        done = engine.poll_results()
    print(json.dumps({"tokens": done[0].tokens}))
"""


@pytest.mark.timeout(300)
def test_request_trace_spans_three_processes(tmp_path, monkeypatch):
    """ISSUE-16 satellite: the span context crosses REAL process
    boundaries — a gateway-process root, a prefill process journaling
    ``prefill_run`` under it, and a decode process whose
    ``engine_admit``/``kv_handoff`` attach via the pickled
    ``KVBundle.sctx`` — assembling into ONE tree spanning 3 procs."""
    import json
    import os
    import subprocess
    import sys

    from dlrover_tpu.common.constants import EnvKey
    from dlrover_tpu.telemetry import trace as trace_mod
    from dlrover_tpu.telemetry.journal import current_ctx, get_journal

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jdir = tmp_path / "journal"
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(jdir))
    monkeypatch.setenv(EnvKey.TRACE_ID, "t3p")
    monkeypatch.setenv(EnvKey.NODE_ID, "gw9")
    driver = tmp_path / "driver.py"
    driver.write_text(_TRACE_DRIVER)

    def child(role, node_id):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env[EnvKey.NODE_ID] = node_id
        proc = subprocess.run(
            [sys.executable, str(driver), role, str(tmp_path)],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=180,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return proc.stdout

    prompt = list(range(19))                   # 3 chunks at P=8
    with get_journal().span("gateway_request", rid=77):
        with open(tmp_path / "req.json", "w") as f:
            json.dump({"prompt": prompt, "sctx": current_ctx()}, f)
        child("prefill", "p9")
        out = child("decode", "d9")
    assert len(json.loads(out.strip().splitlines()[-1])["tokens"]) == 4

    roots = trace_mod.build_forest(
        trace_mod.load_spans([str(jdir)]))
    [req] = trace_mod.find_request_roots(roots, "77")
    names = {n.span.name for n in req.walk()}
    assert {"gateway_request", "prefill_run",
            "engine_admit", "kv_handoff"} <= names
    assert req.n_procs() >= 3
    procs = {n.span.name: n.span.proc for n in req.walk()}
    assert procs["prefill_run"] == "nodep9"
    assert procs["engine_admit"] == "noded9"
    # one tree: nothing from this request dangles as its own root
    dangling = [r for r in roots
                if r is not req and any(
                    n.span.name in names for n in r.walk())]
    assert not dangling


@pytest.mark.timeout(300)
def test_request_trace_phases_sum_to_wall(params, tmp_path, monkeypatch):
    """ISSUE-16 acceptance: one ``/v1/generate`` through the disagg
    gateway yields an assembled trace whose TTFT phase decomposition
    (queue/route/prefill/handoff/decode-first/decode) sums to within 5%
    of the measured request wall time."""
    import json
    import os
    import urllib.request

    from dlrover_tpu.common.constants import EnvKey
    from dlrover_tpu.gateway import GatewayHTTPServer
    from dlrover_tpu.telemetry import trace as trace_mod

    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.setenv(EnvKey.TRACE_ID, "reqwall")
    gw = Gateway(_factory(params, kv_pages=16), replicas=1,
                 prefill_len=8, prefill_replicas=1, seed=7)
    srv = GatewayHTTPServer(gw, host="127.0.0.1",
                            request_timeout_s=120).start()
    try:
        assert _wait(lambda: len(gw.pool.ready_replicas()) == 1
                     and len(gw.prefill_pool.ready_replicas()) == 1)
        url = f"http://127.0.0.1:{srv.port}/v1/generate"

        def generate(max_new):
            body = json.dumps({
                "prompt": list(range(40, 59)), "temperature": 0.0,
                "max_new_tokens": max_new,
            }).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=120) as resp:
                out = json.loads(resp.read())
            return out, time.monotonic() - t0

        generate(4)                        # warmup: compiles settle
        out, wall = generate(32)           # the measured request
        assert len(out["tokens"]) == 32
    finally:
        srv.stop()
        gw.stop()

    roots = trace_mod.build_forest(
        trace_mod.load_spans([str(tmp_path / "journal")]))
    [req] = trace_mod.find_request_roots(roots, str(out["id"]))
    phases = trace_mod.request_phases(req)
    journaled_wall = phases.pop("wall_s")
    # disagg decomposition present, and the phases tile the wall
    assert {"gateway_queue", "gateway_prefill", "gateway_handoff",
            "gateway_decode_first", "gateway_decode"} <= set(phases)
    assert sum(phases.values()) == pytest.approx(journaled_wall,
                                                 abs=1e-5)
    # ...which itself is the measured request wall, within 5% plus a
    # small absolute floor: the client-side clock also counts HTTP
    # connection setup and JSON (de)serialisation, a few ms of fixed
    # overhead outside the traced request that dominates the relative
    # tolerance when the whole request is ~60ms on a loaded box
    assert sum(phases.values()) == pytest.approx(wall, rel=0.05,
                                                 abs=0.02)
    # the prefill pool's own span joined the same tree (same process
    # here, but linked causally via Request/KVBundle sctx)
    assert "prefill_run" in {n.span.name for n in req.walk()}
