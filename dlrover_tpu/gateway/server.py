"""Gateway front door: admission control, dispatch, stdlib HTTP.

The data plane. A request's life::

    HTTP POST /v1/generate  (or Gateway.submit from Python)
      -> admission: estimated wait vs deadline, 429 + Retry-After past it
      -> seed minting: results are a function of (params, prompt,
         sampling, seed) — never of which replica serves them
      -> router: least-outstanding-slots with prefix-cache affinity
      -> replica decode thread (gateway/pool.py) -> Future resolves

Admission bound derivation: with ``p`` requests pending (queued +
in-flight), EWMA per-request service time ``s`` and ``S`` decode slots
across READY replicas, a new request waits ~``p*s/S`` before its decode
finishes. Admission holds that estimate under ``deadline_s``; the
implied queue bound is ``deadline_s * S / s`` requests, so the bound
tracks capacity (grows when the autoscaler adds replicas, shrinks when
requests get longer) instead of being a magic constant. Rejections
carry ``Retry-After`` sized to when the backlog is expected to fit
again — open-loop clients get backpressure they can obey rather than a
timeout they discover.

A replica kill mid-decode costs latency, not correctness: the pool
hands the dead replica's unfinished work back and the gateway re-routes
it; minted seeds make the re-decode identical to what the dead replica
would have produced.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional, Sequence

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.gateway.pool import ReplicaPool, RequestWork
from dlrover_tpu.gateway.router import Router
from dlrover_tpu.serving import SamplingParams
from dlrover_tpu.telemetry.exposition import CONTENT_TYPE, render
from dlrover_tpu.telemetry.journal import (
    current_trace_id,
    format_ctx,
    get_journal,
    mint_span_id,
    should_sample,
)
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

_requests_total = registry().counter(
    "dlrover_tpu_gateway_requests_total",
    "gateway requests by outcome code (200/429/500)",
    label_names=("code",),
)
_request_seconds = registry().histogram(
    "dlrover_tpu_gateway_request_seconds",
    "submit -> completion latency per gateway request",
    label_names=("finish",),
)
_queue_seconds = registry().histogram(
    "dlrover_tpu_gateway_queue_seconds",
    "admission -> replica-dispatch wait per request",
)
_queue_depth = registry().gauge(
    "dlrover_tpu_gateway_queue_depth",
    "requests admitted and not yet completed",
)
_resubmitted_total = registry().counter(
    "dlrover_tpu_gateway_resubmitted_total",
    "requests re-routed after an abrupt replica death",
)
_embedding_lookups_total = registry().counter(
    "dlrover_tpu_gateway_embedding_lookups_total",
    "embedding-route lookups by outcome code (200/400/503)",
    label_names=("code",),
)


class AdmissionError(RuntimeError):
    """Backpressure: retry after ``retry_after_s`` (HTTP 429)."""

    def __init__(self, retry_after_s: float, message: str):
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass
class GatewayResult:
    id: int
    tokens: list[int]
    finish_reason: str
    replica_id: int
    attempts: int
    total_s: float
    queue_s: float
    prefill_s: float
    decode_s: float
    # monotonic arrival stamp per token (the bench derives TTFT and
    # inter-token-latency percentiles from these)
    token_times: list = dataclasses.field(default_factory=list)
    # a block-diffusion replica: the denoising pass that unmasked each
    # token (``serving.engine.Result.unmask_steps``); a block's tokens
    # arrive together, so their ``token_times`` lie a callback apart
    unmask_steps: list = dataclasses.field(default_factory=list)


class AdmissionController:
    """Deadline-derived bounded queue (see module docstring for the
    bound's derivation)."""

    def __init__(self, deadline_s: float = 30.0,
                 init_request_s: float = 0.5,
                 ewma_alpha: float = 0.2):
        self.deadline_s = deadline_s
        self._alpha = ewma_alpha
        self._ewma_s = init_request_s
        self._pending = 0
        self._lock = threading.Lock()

    @property
    def pending(self) -> int:
        return self._pending

    @property
    def ewma_request_s(self) -> float:
        return self._ewma_s

    def estimated_wait_s(self, slots_total: int) -> float:
        with self._lock:
            return self._pending * self._ewma_s / max(1, slots_total)

    def try_admit(self, slots_total: int) -> None:
        """Admit or raise ``AdmissionError`` with a Retry-After."""
        with self._lock:
            est_wait = (self._pending * self._ewma_s
                        / max(1, slots_total))
            if est_wait > self.deadline_s:
                retry = max(1.0, est_wait - self.deadline_s)
                raise AdmissionError(
                    retry, f"estimated wait {est_wait:.1f}s exceeds "
                           f"deadline {self.deadline_s:.1f}s "
                           f"({self._pending} pending)",
                )
            self._pending += 1
            _queue_depth.set(self._pending)

    def release(self, service_s: float | None = None) -> None:
        with self._lock:
            self._pending = max(0, self._pending - 1)
            _queue_depth.set(self._pending)
            if service_s is not None:
                self._ewma_s += self._alpha * (service_s - self._ewma_s)


class Gateway:
    """Pool + router + admission behind one ``submit``.

    ``engine_factory`` builds one ``serving.InferenceEngine`` per
    replica (runs on the replica's thread); ``prefill_len`` must match
    the engines' chunk size so router affinity keys line up with the
    engines' prefix-cache keys.

    ``prefill_replicas > 0`` disaggregates: a PREFILL pool
    (``serving.PrefillEngine`` replicas) runs prompts and ships
    page-granular KV bundles; the main pool becomes the DECODE pool
    and installs bundles via ``submit_prefilled``. Prefix affinity
    routes to the prefill pool (that's where the prefix caches live);
    decode dispatch is pure least-outstanding. The two pools scale
    independently (``DisaggAutoscaler``) — and because the minted seed,
    chunk program and install path are identical, a request's tokens
    are bit-identical to the unified path.
    """

    def __init__(self, engine_factory, *, replicas: int = 1,
                 prefill_len: int = 64,
                 prefill_replicas: int = 0,
                 prefill_engine_factory=None,
                 admission_deadline_s: float = 30.0,
                 init_request_s: float = 0.5,
                 dispatch_timeout_s: float = 120.0,
                 seed: int = 0,
                 preemption_file: str | None = None,
                 health_interval_s: float = 0.5):
        self.router = Router(prefill_len)
        self.admission = AdmissionController(
            deadline_s=admission_deadline_s,
            init_request_s=init_request_s,
        )
        self.disaggregated = prefill_replicas > 0
        self.pool = ReplicaPool(
            engine_factory, self._on_done, self._resubmit,
            on_error=self._fail,
            health_interval_s=health_interval_s,
            preemption_file=preemption_file,
            name="decode" if self.disaggregated else "serving",
        )
        self.prefill_pool = None
        if self.disaggregated:
            from dlrover_tpu.serving import PrefillEngine

            factory = prefill_engine_factory or (
                lambda: PrefillEngine(engine_factory())
            )
            self.prefill_pool = ReplicaPool(
                factory, self._on_prefilled, self._resubmit,
                on_error=self._fail,
                health_interval_s=health_interval_s,
                preemption_file=preemption_file,
                name="prefill",
            )
        self._seed = seed
        # set by gateway.control.MasterLink when a master is attached
        self.master_link = None
        self._dispatch_timeout_s = dispatch_timeout_s
        self._ids_lock = threading.Lock()
        self._next_id = 0
        self._undispatched: deque[RequestWork] = deque()
        self._undispatched_lock = threading.Lock()
        self._stop = threading.Event()
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="gateway-dispatcher",
            daemon=True,
        )
        self._dispatcher.start()
        self.pool.ensure(replicas)
        if self.prefill_pool is not None:
            self.prefill_pool.ensure(prefill_replicas)

    # ----------------------------------------------------------- user API

    def submit(self, prompt: Sequence[int],
               params: SamplingParams | None = None) -> Future:
        """Admit + dispatch; returns a Future[GatewayResult]. Raises
        ``AdmissionError`` (429) past the backpressure bound."""
        params = params or SamplingParams()
        try:
            self.admission.try_admit(self.pool.slots_total())
        except AdmissionError:
            _requests_total.labels("429").inc()
            raise
        with self._ids_lock:
            rid = self._next_id
            self._next_id += 1
        if params.seed is None:
            params = dataclasses.replace(
                params, seed=self._mint_seed(rid)
            )
        work = RequestWork(
            id=rid, prompt=list(prompt), params=params,
            future=Future(), submit_t=time.monotonic(),
        )
        if get_journal().enabled and should_sample(f"req:{rid}"):
            # pre-mint the trace root (§27): the prefill/decode engines
            # attach children under it while the request is in flight;
            # the retroactive gateway_request point reuses this id
            work.span_id = mint_span_id("gateway_request")
            work.sctx = format_ctx(current_trace_id(), work.span_id)
        if not self._try_dispatch(work):
            with self._undispatched_lock:
                self._undispatched.append(work)
        return work.future

    def generate(self, prompt: Sequence[int],
                 params: SamplingParams | None = None,
                 timeout: float | None = None) -> GatewayResult:
        return self.submit(prompt, params).result(timeout)

    def stats(self) -> dict:
        states = [r.state.value for r in self.pool.replicas()]
        # §29: pool-wide observatory aggregate (health-tick product) +
        # the prefix-cache hit rate across every pool this gateway runs
        obs = dict(self.pool.observatory or {})
        hits = obs.get("prefix_cache_hits", 0)
        queries = obs.get("prefix_cache_queries", 0)
        if self.prefill_pool is not None:
            pf_obs = self.prefill_pool.observatory or {}
            hits += pf_obs.get("prefix_cache_hits", 0)
            queries += pf_obs.get("prefix_cache_queries", 0)
        hit_rate = round(hits / queries, 4) if queries else 0.0
        if self.prefill_pool is not None:
            pf = self.prefill_pool
            return {
                "prefix_cache_hit_rate": hit_rate,
                "serving_observatory": obs,
                "degraded": bool(self.master_link is not None
                                 and self.master_link.degraded),
                "disaggregated": True,
                "replicas": {s: states.count(s) for s in set(states)},
                "ready": len(self.pool.ready_replicas()),
                "prefill_ready": len(pf.ready_replicas()),
                "prefill_backlog": pf.outstanding_total(),
                "slots_total": self.pool.slots_total(),
                "slot_occupancy": round(self.pool.occupancy(), 4),
                "queue_depth": self.admission.pending,
                "ewma_request_s": round(
                    self.admission.ewma_request_s, 4),
                "estimated_wait_s": round(
                    self.admission.estimated_wait_s(
                        self.pool.slots_total()
                    ), 4,
                ),
            }
        return {
            "degraded": bool(self.master_link is not None
                             and self.master_link.degraded),
            "prefix_cache_hit_rate": hit_rate,
            "serving_observatory": obs,
            "replicas": {s: states.count(s) for s in set(states)},
            "ready": len(self.pool.ready_replicas()),
            "slots_total": self.pool.slots_total(),
            "slot_occupancy": round(self.pool.occupancy(), 4),
            "queue_depth": self.admission.pending,
            "ewma_request_s": round(self.admission.ewma_request_s, 4),
            "estimated_wait_s": round(
                self.admission.estimated_wait_s(
                    self.pool.slots_total()
                ), 4,
            ),
        }

    def undispatched_counts(self) -> tuple[int, int]:
        """(awaiting-prefill, awaiting-decode) requests no replica has
        accepted yet — the disaggregated autoscaler's backlog split."""
        with self._undispatched_lock:
            pre = sum(1 for w in self._undispatched
                      if w.bundle is None)
            return pre, len(self._undispatched) - pre

    def request_hist_snapshot(self) -> tuple[tuple[float, ...], list[int],
                                             int, float]:
        """(bounds, per-bucket counts incl +Inf, count, sum) of the
        request-latency histogram, merged over finish labels — the
        autoscaler's p95 source."""
        bounds = _request_seconds.buckets
        merged = [0] * (len(bounds) + 1)
        count, total = 0, 0.0
        for sample in _request_seconds.samples():
            for i, n in enumerate(sample["buckets"]):
                merged[i] += n
            count += sample["count"]
            total += sample["sum"]
        return bounds, merged, count, total

    def stop(self) -> None:
        self._stop.set()
        if self.prefill_pool is not None:
            self.prefill_pool.stop()
        self.pool.stop()
        with self._undispatched_lock:
            pending, self._undispatched = list(self._undispatched), deque()
        for work in pending:
            self._fail(work, RuntimeError("gateway stopped"))

    # ----------------------------------------------------------- dispatch

    def _mint_seed(self, rid: int) -> int:
        # a request's continuation must not depend on which replica
        # serves it (or re-serves it after a kill): derive the sampling
        # seed from (gateway seed, request id) so every engine decodes
        # the identical stream
        digest = hashlib.blake2s(
            f"{self._seed}:{rid}".encode(), digest_size=4
        ).digest()
        return int.from_bytes(digest, "big")

    def _try_dispatch(self, work: RequestWork) -> bool:
        if self.prefill_pool is not None and work.bundle is None:
            # disaggregated: prefix affinity targets the PREFILL pool
            # (its engines own the prefix caches the affinity exists
            # for); the bundle comes back through _on_prefilled
            replica = self.router.route(
                work.prompt, self.prefill_pool.ready_replicas()
            )
            if replica is None or not replica.submit(work):
                return False
            self.router.record(work.prompt, replica.id)
            return True
        if self.prefill_pool is not None:
            # decode dispatch: the KV arrives with the bundle, so pure
            # least-outstanding beats any affinity
            replicas = self.pool.ready_replicas()
            if not replicas:
                return False
            replica = min(replicas,
                          key=lambda r: (r.outstanding, r.id))
            return replica.submit(work)
        replica = self.router.route(
            work.prompt, self.pool.ready_replicas()
        )
        if replica is None or not replica.submit(work):
            return False
        self.router.record(work.prompt, replica.id)
        return True

    def _on_prefilled(self, work: RequestWork, res: Any) -> None:
        """Prefill-pool completion hook: attach the KV bundle and hand
        the request to the decode pool."""
        work.prefill_done_t = time.monotonic()
        work.bundle = res.bundle
        if not self._try_dispatch(work):
            with self._undispatched_lock:
                self._undispatched.append(work)

    def _dispatch_loop(self) -> None:
        # retries work that found no READY replica (all starting, or a
        # kill emptied the pool until the autoscaler restores it)
        while not self._stop.wait(0.05):
            with self._undispatched_lock:
                pending = list(self._undispatched)
                self._undispatched.clear()
            for work in pending:
                if self._stop.is_set():
                    break
                age = time.monotonic() - work.submit_t
                if age > self._dispatch_timeout_s:
                    self._fail(work, RuntimeError(
                        f"request {work.id} undispatchable for "
                        f"{age:.0f}s (no serving replica)"
                    ))
                elif not self._try_dispatch(work):
                    with self._undispatched_lock:
                        self._undispatched.append(work)

    def _resubmit(self, orphans: list[RequestWork]) -> None:
        """Pool hook: a replica died abruptly with this work unfinished."""
        _resubmitted_total.inc(len(orphans))
        for work in orphans:
            self.router.forget(work.replica_id)
            work.attempts += 1
            work.first_token_t = 0.0
            work.token_times = []
            work.decode_dispatch_t = 0.0
            if work.bundle is None:
                work.prefill_done_t = 0.0
            with self._undispatched_lock:
                self._undispatched.append(work)

    # -------------------------------------------------------- completion

    def _on_done(self, work: RequestWork, res: Any) -> None:
        done_t = time.monotonic()
        total = done_t - work.submit_t
        queue_s = max(0.0, work.dispatch_t - work.submit_t)
        first = work.first_token_t or done_t
        prefill_s = max(0.0, first - work.dispatch_t)
        decode_s = max(0.0, done_t - first)
        self.admission.release(done_t - work.dispatch_t)
        _requests_total.labels("200").inc()
        _request_seconds.labels(res.finish_reason).observe(total)
        _queue_seconds.observe(queue_s)
        self._journal_request(work, res, done_t)
        if not work.future.done():
            work.future.set_result(GatewayResult(
                id=work.id, tokens=list(res.tokens),
                finish_reason=res.finish_reason,
                replica_id=work.replica_id, attempts=work.attempts,
                total_s=total, queue_s=queue_s, prefill_s=prefill_s,
                decode_s=decode_s,
                token_times=list(work.token_times),
                unmask_steps=list(getattr(res, "unmask_steps", ())),
            ))

    def _journal_request(self, work: RequestWork, res: Any,
                         done_t: float) -> None:
        """Retroactive causal tree of one finished request (§27): the
        pre-minted ``gateway_request`` root plus phase children placed
        at their true wall times, so the phase durations exactly tile
        [submit, done] and ``telemetry/trace.py`` can decompose TTFT.
        Skipped entirely when the request was head-sampled out."""
        journal = get_journal()
        if not journal.enabled or not work.span_id:
            return
        now_wall = time.time()

        def wall(mono: float) -> float:
            # monotonic stamp -> the wall time the same instant had
            return round(now_wall - (done_t - mono), 6)

        total = done_t - work.submit_t
        first = work.first_token_t or done_t
        parent = journal.emit(
            "gateway_request", dur=total, rid=work.id,
            replica=work.replica_id, attempts=work.attempts,
            finish=res.finish_reason, tokens=len(res.tokens),
            span_id=work.span_id, disagg=work.bundle is not None,
        )
        journal.emit("gateway_queue", parent=parent,
                     dur=max(0.0, work.dispatch_t - work.submit_t),
                     t=wall(work.dispatch_t))
        journal.emit("gateway_route", parent=parent, dur=0.0,
                     replica=work.replica_id, t=wall(work.dispatch_t))
        if work.bundle is not None and work.prefill_done_t:
            # disaggregated TTFT: prefill chunks, bundle handoff +
            # decode-pool queue, then install-to-first-token
            decode_disp = work.decode_dispatch_t or work.prefill_done_t
            journal.emit(
                "gateway_prefill", parent=parent,
                dur=max(0.0, work.prefill_done_t - work.dispatch_t),
                t=wall(work.prefill_done_t))
            journal.emit(
                "gateway_handoff", parent=parent,
                dur=max(0.0, decode_disp - work.prefill_done_t),
                t=wall(decode_disp))
            journal.emit(
                "gateway_decode_first", parent=parent,
                dur=max(0.0, first - decode_disp), t=wall(first))
        else:
            journal.emit(
                "gateway_prefill", parent=parent,
                dur=max(0.0, first - work.dispatch_t), t=wall(first))
        journal.emit("gateway_decode", parent=parent,
                     dur=max(0.0, done_t - first), t=wall(done_t))

    def _fail(self, work: RequestWork, exc: Exception) -> None:
        self.admission.release()
        _requests_total.labels("500").inc()
        if not work.future.done():
            work.future.set_exception(exc)


class GatewayHTTPServer:
    """JSON-over-HTTP front door on ``ThreadingHTTPServer``.

    - ``POST /v1/generate``: ``{"prompt": [ids], "max_new_tokens"?,
      "temperature"?, "top_k"?, "top_p"?, "eos_id"?, "seed"?}`` ->
      ``{"id", "tokens", "finish_reason", "replica", "attempts"}``;
      429 + ``Retry-After`` under backpressure.
    - ``POST /v1/embedding/lookup`` (with ``embedding_client``):
      ``{"ids": [[...]]}`` -> ``{"values", "version",
      "applied_version", "staleness"}`` — rows served from the LIVE
      training ring through a read-only, version-pinned fabric client
      (DESIGN.md §25); missing ids score as zero vectors, never
      materialize rows. 503 while the ring is unreachable.
    - ``GET /healthz``: replica/queue summary; 503 with no READY replica.
    - ``GET /metrics``: Prometheus text (``dlrover_tpu_gateway_*`` et al).

    ``gateway`` may be None for an embedding-only front door (the
    recsys serving example): the generate route then answers 503.
    """

    def __init__(self, gateway: Optional[Gateway], *,
                 host: str = "0.0.0.0", port: int = 0,
                 request_timeout_s: float = 300.0,
                 embedding_client=None):
        outer = self
        self.gateway = gateway
        self.embedding_client = embedding_client
        self._request_timeout_s = request_timeout_s

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args) -> None:  # no per-request spam
                pass

            def _json(self, code: int, payload: dict,
                      headers: dict | None = None) -> None:
                body = json.dumps(payload).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _embedding_lookup(self) -> None:
                client = outer.embedding_client
                if client is None:
                    _embedding_lookups_total.labels("503").inc()
                    self._json(503, {"error": "no embedding ring "
                               "attached to this gateway"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(length))
                    ids = req["ids"]
                    if not isinstance(ids, list) or not ids:
                        raise ValueError("ids must be a non-empty list")
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    _embedding_lookups_total.labels("400").inc()
                    self._json(400, {"error": f"bad request: {e}"})
                    return
                try:
                    import numpy as np

                    values, info = client.lookup_with_info(
                        np.asarray(ids, dtype=np.int64),
                        init_missing=False,
                    )
                except Exception as e:  # noqa: BLE001 - report to client
                    _embedding_lookups_total.labels("503").inc()
                    self._json(503, {
                        "error": f"{type(e).__name__}: {e}",
                    })
                    return
                _embedding_lookups_total.labels("200").inc()
                self._json(200, {
                    "values": values.tolist(),
                    "version": info["version"],
                    "applied_version": info["applied_version"],
                    "staleness": info["staleness"],
                })

            def do_GET(self) -> None:  # noqa: N802 - stdlib API
                path = self.path.split("?")[0]
                if path == "/healthz":
                    if outer.gateway is None:
                        ok = outer.embedding_client is not None
                        self._json(200 if ok else 503, {
                            "ready": ok,
                            "status": "embedding_only" if ok
                            else "no_backends",
                        })
                        return
                    stats = outer.gateway.stats()
                    code = 200 if stats["ready"] else 503
                    stats["status"] = "ok" if stats["ready"] else "no_replicas"
                    self._json(code, stats)
                elif path == "/metrics":
                    body = render().encode("utf-8")
                    self.send_response(200)
                    self.send_header("Content-Type", CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def do_POST(self) -> None:  # noqa: N802 - stdlib API
                path = self.path.split("?")[0]
                if path == "/v1/embedding/lookup":
                    self._embedding_lookup()
                    return
                if path not in ("/v1/generate", "/generate"):
                    self.send_error(404)
                    return
                if outer.gateway is None:
                    self._json(503, {"error": "no decode backend "
                               "(embedding-only gateway)"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(length))
                    prompt = [int(t) for t in req["prompt"]]
                    if not prompt:
                        raise ValueError("empty prompt")
                    params = SamplingParams(
                        temperature=float(req.get("temperature", 1.0)),
                        top_k=int(req.get("top_k", 0)),
                        top_p=float(req.get("top_p", 1.0)),
                        max_new_tokens=int(req.get("max_new_tokens", 64)),
                        eos_id=(int(req["eos_id"])
                                if req.get("eos_id") is not None else None),
                        seed=(int(req["seed"])
                              if req.get("seed") is not None else None),
                    )
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    self._json(400, {"error": f"bad request: {e}"})
                    return
                try:
                    result = outer.gateway.generate(
                        prompt, params, timeout=outer._request_timeout_s
                    )
                except AdmissionError as e:
                    self._json(429, {
                        "error": str(e),
                        "retry_after_s": round(e.retry_after_s, 1),
                    }, headers={
                        "Retry-After": str(int(e.retry_after_s + 0.999)),
                    })
                    return
                except (FutureTimeout, TimeoutError):
                    self._json(504, {"error": "generation timed out"})
                    return
                except Exception as e:  # noqa: BLE001 - report to client
                    self._json(500, {"error": f"{type(e).__name__}: {e}"})
                    return
                self._json(200, {
                    "id": result.id,
                    "tokens": result.tokens,
                    "finish_reason": result.finish_reason,
                    "replica": result.replica_id,
                    "attempts": result.attempts,
                })

        class _Server(ThreadingHTTPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = _Server((host, port), _Handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> "GatewayHTTPServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="gateway-http",
            daemon=True,
        )
        self._thread.start()
        logger.info("gateway HTTP front door on port %d", self.port)
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
