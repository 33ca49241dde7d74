"""Serving memory observatory: measure before building ROADMAP-3.

PAPER.md's top layer is the thesis that optimization decisions should
be driven by collected runtime measurements, and this repo has proven
the pattern twice (the §23 control-plane observatory named the
bottleneck PR-17's rack tier then fixed; the §24 autopilot plans from
measured step history). ROADMAP item 3 — speculative decoding +
copy-on-write KV pages, the two multiplicative levers on
``serving_toks_per_s`` — had no such instrument. This module is that
instrument: three **measure-only** probes (zero behavior change,
pinned by a token-identity test) that quantify each lever's headroom
on live traffic before either is built (DESIGN.md §29):

1. **KV page-pool accounting** — free/used/high-water page gauges,
   pages-per-request and park/resume-churn histograms, and the wall
   time admission spends blocked on page exhaustion. Periodic
   ``kv_pool`` journal samples become Perfetto counter lanes
   (``telemetry/timeline.py``), so page pressure reads alongside the
   request span lanes.
2. **Prefix-share headroom** (the COW case) — blake2s chain hashes
   over each live slot's page-aligned token-id spans. A page is
   *shareable* when its chained digest (which covers the whole prefix
   through that page — KV content depends on every preceding token,
   so equal page content alone is not shareable) appears in ≥ 2 live
   slots. Yields ``shareable_frac``, the would-be effective-capacity
   multiplier under copy-on-write (total/unique pages), and prefix
   families keyed by leading-page content — the tenant proxy: requests
   sharing a system prompt share their first page(s), so family sizes
   recover per-tenant sharing without a tenant field in the API.
3. **Draft-acceptance shadowing** (the spec-decode case) — a cheap
   host-side shadow predictor (order-k n-gram over the request's OWN
   prompt + generated context, deterministic, no RNG) scores every
   emitted decode token. The resulting ``draft_accept_rate`` and
   run-length histogram of consecutive accepts are the measured prior
   for choosing draft depth k later. A prompt enters the predictor a
   prefill chunk at a time, on the engine's thread, between the chunk's
   dispatch and the wait for it (``note_prefilled``); the install
   (``note_admitted``) counts only what no chunk carried, and from then
   on the predictor holds the whole prompt, as one built whole would.

The observatory is on by default (``DLROVER_TPU_SERVING_OBSERVATORY=0``
disables it) and touches only host-side bookkeeping: it never reads
device arrays, never changes which compiled programs run, and never
reorders admission — the identity test in tests/test_observatory.py
pins exactly that.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import Counter
from typing import Any

from dlrover_tpu.telemetry.journal import get_journal
from dlrover_tpu.telemetry.metrics import registry

_pages_free = registry().gauge(
    "dlrover_tpu_engine_kv_pages_free",
    "KV pool pages currently free, per engine",
    label_names=("engine",),
)
_pages_used = registry().gauge(
    "dlrover_tpu_engine_kv_pages_used",
    "KV pool pages currently leased, per engine",
    label_names=("engine",),
)
_pages_high_water = registry().gauge(
    "dlrover_tpu_engine_kv_pages_high_water",
    "max pages ever simultaneously leased, per engine",
    label_names=("engine",),
)
_shareable_frac_g = registry().gauge(
    "dlrover_tpu_engine_kv_shareable_frac",
    "fraction of live full pages whose chained content hash appears "
    "in >= 2 live slots (the copy-on-write headroom)",
    label_names=("engine",),
)
_accept_rate_g = registry().gauge(
    "dlrover_tpu_engine_draft_accept_rate",
    "fraction of emitted decode tokens the n-gram shadow predictor "
    "guessed (the speculative-decoding acceptance prior)",
    label_names=("engine",),
)
_pages_per_request = registry().histogram(
    "dlrover_tpu_engine_kv_pages_per_request",
    "pages leased per admitted request",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
_park_churn = registry().histogram(
    "dlrover_tpu_engine_kv_park_churn",
    "park + resume events over one request's lifetime",
    buckets=(0, 1, 2, 4, 8, 16, 32),
)
_admission_wait = registry().histogram(
    "dlrover_tpu_engine_kv_admission_wait_seconds",
    "wall time the queue head spent blocked on page-pool exhaustion",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0),
)
_accept_run_len = registry().histogram(
    "dlrover_tpu_engine_draft_accept_run_length",
    "consecutive shadow-predictor accepts per run (the measured prior "
    "for speculative draft depth)",
    buckets=(1, 2, 4, 8, 16, 32, 64),
)
_cow_saved_g = registry().gauge(
    "dlrover_tpu_engine_kv_cow_pages_saved",
    "page-table entries currently deduped onto shared physical pages "
    "(realized copy-on-write savings), per engine",
    label_names=("engine",),
)
_spec_rate_g = registry().gauge(
    "dlrover_tpu_spec_accept_rate_live",
    "live speculative-draft acceptance: accepted / scored REAL draft "
    "tokens across verify steps, per engine",
    label_names=("engine",),
)

# pow2 run-length buckets mirrored host-side so the observatory can
# derive p50/p95 for its own journal samples without scraping
_RUN_BOUNDS = (1, 2, 4, 8, 16, 32, 64)


class PrefixDigestStore:
    """Per-request incremental chain digests at page boundaries (§31).

    One blake2s hasher per live request, fed each token exactly once
    (prompt at admission, emitted tokens from the decode host loop); a
    digest lands in the per-request list at every FULL page boundary.
    Both the engine's COW sharing index and the observatory's
    prefix-share sample read these lists — chain hashing happens once,
    never per sample. Digest scheme is identical to
    ``page_share_stats``: boundary p's digest covers the whole prefix
    ``tokens[0 : (p+1)*page_size]``.
    """

    def __init__(self, page_size: int) -> None:
        self.page_size = max(1, int(page_size))
        self._hashers: dict[int, Any] = {}
        self._counts: dict[int, int] = {}
        self._pages: dict[int, list[bytes]] = {}

    def start(self, rid: int, tokens) -> None:
        """Open a request's chain and absorb its prompt (idempotent —
        blocked admissions re-probe without double hashing)."""
        if rid in self._hashers:
            return
        self._hashers[rid] = hashlib.blake2s()
        self._counts[rid] = 0
        self._pages[rid] = []
        for tok in tokens:
            self.extend(rid, tok)

    def extend(self, rid: int, tok: int) -> None:
        h = self._hashers.get(rid)
        if h is None:
            return
        h.update(int(tok).to_bytes(8, "little", signed=True))
        self._counts[rid] += 1
        if self._counts[rid] % self.page_size == 0:
            # blake2s digest() does not finalize: the chain continues
            self._pages[rid].append(h.digest())

    def pages(self, rid: int) -> list[bytes]:
        """Full-page chain digests absorbed so far (never a copy the
        caller may mutate — treat as read-only)."""
        return self._pages.get(rid, [])

    def drop(self, rid: int) -> None:
        self._hashers.pop(rid, None)
        self._counts.pop(rid, None)
        self._pages.pop(rid, None)


def digest_share_stats(slot_digests) -> dict:
    """Prefix-share headroom over precomputed per-slot chain-digest
    lists (one ``PrefixDigestStore.pages`` list per live request) —
    the O(pages) sample path, no token rehashing."""
    owners: dict[bytes, set[int]] = {}
    first_page: list[bytes] = []
    total = 0
    for sid, digests in enumerate(slot_digests):
        for p, digest in enumerate(digests):
            owners.setdefault(digest, set()).add(sid)
            if p == 0:
                first_page.append(digest)
            total += 1
    shareable = sum(
        len(s) for s in owners.values() if len(s) >= 2
    )
    unique = len(owners)
    families = Counter(first_page)
    sizes = sorted(families.values(), reverse=True)
    return {
        "total_pages": total,
        "unique_pages": unique,
        "shareable_pages": shareable,
        "shareable_frac": (shareable / total) if total else 0.0,
        # effective capacity multiplier if shared pages were COW: the
        # same live set would fit in unique_pages physical pages
        "cow_multiplier": (total / unique) if unique else 1.0,
        "families": len(sizes),
        "largest_family": sizes[0] if sizes else 0,
        "family_sizes": sizes[:8],
    }


def page_share_stats(slot_tokens, page_size: int) -> dict:
    """Prefix-share headroom over live slots' token streams.

    ``slot_tokens`` is one token-id list per live slot (prompt +
    emitted). Pages are hashed with a per-slot blake2s CHAIN — digest
    at page boundary p covers tokens[0 : (p+1)*page_size] — because a
    KV page is only truly shareable when the entire prefix through it
    matches, not merely the page's own tokens. Only full pages count;
    a partial trailing page is never shareable.
    """
    slot_digests = []
    for toks in slot_tokens:
        h = hashlib.blake2s()
        digests = []
        for p in range(len(toks) // page_size):
            lo = p * page_size
            for t in toks[lo: lo + page_size]:
                h.update(int(t).to_bytes(8, "little", signed=True))
            digests.append(h.digest())
        slot_digests.append(digests)
    return digest_share_stats(slot_digests)


# n-gram order of the engine's draft-acceptance shadow predictor
SHADOW_ORDER = 3


class ShadowPredictor:
    """Order-k n-gram draft shadow over one request's own context.

    Deterministic by construction (no RNG: ties break to the smallest
    token id; back-off is longest-match k→1), so the acceptance
    estimate is reproducible and the measure-only pin is trivially
    safe — the predictor only ever *observes* emitted tokens.

    A table entry depends only on a position and the tokens before it,
    never on when it was counted, so a prompt may enter the tables in
    slices: ``whole=False`` holds the prompt as context and counts none
    of it, ``index(lo, hi)`` counts a slice (the engine: under the
    prefill chunk that carries those tokens) and ``finish()`` counts
    what no slice covered (the engine: at the install). After
    ``finish()`` the predictor is the one built whole, table for table;
    before it, ``predict`` / ``draft`` / ``observe`` are not to be asked.
    """

    def __init__(self, order: int, prompt, whole: bool = True) -> None:
        self.order = max(1, int(order))
        self._ctx: list[int] = [int(t) for t in prompt]
        # context (j tokens) -> follower -> times seen, for j = 1..order
        self._tables: list[dict[tuple, dict[int, int]]] = [
            {} for _ in range(self.order)
        ]
        # the prompt positions counted so far, one interval (a prefill's
        # chunks each start where the last ended); None once all are
        self._indexed: tuple[int, int] | None = (0, 0)
        self.scored = 0
        self.accepted = 0
        if whole:
            self.finish()

    def _count(self, lo: int, hi: int) -> None:
        """Count the followers at context positions ``lo..hi``."""
        ctx, tables, order = self._ctx, self._tables, self.order
        for i in range(lo, hi):
            tok = ctx[i]
            for j in range(1, min(order, i) + 1):
                key = tuple(ctx[i - j: i])
                followers = tables[j - 1].get(key)
                if followers is None:
                    tables[j - 1][key] = {tok: 1}
                else:
                    followers[tok] = followers.get(tok, 0) + 1

    def index(self, lo: int, hi: int) -> int:
        """Count prompt positions ``lo..hi``; returns how many were
        counted here. A slice that does not start where the counted
        interval ends counts nothing: ``finish()`` has it."""
        done = self._indexed
        if done is None:
            return 0
        if done[0] == done[1]:
            done = (lo, lo)
        hi = min(hi, len(self._ctx))
        if lo != done[1] or hi <= lo:
            return 0
        self._count(lo, hi)
        self._indexed = (done[0], hi)
        return hi - lo

    def finish(self) -> int:
        """Count the prompt positions no ``index`` slice covered (all of
        them for a prompt that had none); returns how many. A finished
        predictor has nothing left, so a second call counts nothing."""
        if self._indexed is None:
            return 0
        lo, hi = self._indexed
        self._indexed = None
        self._count(0, lo)
        self._count(hi, len(self._ctx))
        return lo + len(self._ctx) - hi

    def _absorb(self, tok: int) -> None:
        self._ctx.append(tok)
        self._count(len(self._ctx) - 1, len(self._ctx))

    def _predict_ctx(self, ctx, min_order: int = 1):
        for j in range(min(self.order, len(ctx)), 0, -1):
            if j < min_order:
                break
            followers = self._tables[j - 1].get(tuple(ctx[-j:]))
            if followers:
                return min(
                    followers.items(), key=lambda kv: (-kv[1], kv[0])
                )[0]
        return None

    def predict(self):
        """What the draft would emit next, or None with no evidence."""
        return self._predict_ctx(self._ctx)

    def draft(self, k: int, min_order: int = 2) -> list[int]:
        """Up to k self-drafted next tokens (§31): rolling
        longest-match lookups over context + the draft's own guesses,
        WITHOUT absorbing them — the tables only ever learn emitted
        truth. Zero RNG; stops early when evidence runs out.

        ``min_order`` gates the FIRST guess on longest-match depth:
        order-1 backoff fires on almost any context but measures ~2x
        worse precision than an order->=2 match, and a fired-but-wrong
        draft costs a wasted wide verify — the live drafter only
        speaks when the evidence is strong (rolled continuations may
        back off; the leading match already anchors them)."""
        ctx = list(self._ctx)
        out: list[int] = []
        for i in range(max(0, int(k))):
            guess = self._predict_ctx(
                ctx, min_order if i == 0 else 1)
            if guess is None:
                break
            out.append(guess)
            ctx.append(guess)
        return out

    def observe(self, tok: int) -> bool:
        """Score one emitted token against the draft, then absorb it;
        returns whether the draft would have been accepted."""
        guess = self.predict()
        self.scored += 1
        hit = guess == tok
        if hit:
            self.accepted += 1
        self._absorb(int(tok))
        return hit


class ServingObservatory:
    """Per-engine measurement state + the periodic ``kv_pool`` sample.

    All hooks run on the engine's single decode thread; ``snapshot()``
    (the gateway health-tick reader) only copies the last published
    sample under a small lock.

    A request's shadow is made at its first prefill chunk
    (``note_prefilled``: the chunk's tokens, indexed while the device
    runs the chunk) or, for an admission that runs none, at its install
    (``note_admitted``: whatever of the prompt is not yet indexed). It is
    whole from the install on, which is when ``observe_token`` and the
    engine's speculation first read it, and goes at ``note_retire``.
    """

    def __init__(self, engine, *, sample_every: int = 32) -> None:
        self.engine = engine
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        self._steps = 0
        self._shadow: dict[int, ShadowPredictor] = {}
        self._run_cur: dict[int, int] = {}
        self._run_counts = [0] * (len(_RUN_BOUNDS) + 1)
        self._runs_closed = 0
        self._churn: dict[int, int] = {}
        self._blocked_since: float | None = None
        self.high_water = 0
        self.scored = 0
        self.accepted = 0
        self._last_sample: dict = {}

    # ------------------------------------------------- page-pool hooks

    def note_page_blocked(self) -> None:
        """Queue head could not lease its pages this tick."""
        if self._blocked_since is None:
            self._blocked_since = time.monotonic()

    def note_pages_leased(self, rid: int, n_pages: int) -> None:
        if self._blocked_since is not None:
            _admission_wait.observe(
                time.monotonic() - self._blocked_since
            )
            self._blocked_since = None
        if n_pages:
            _pages_per_request.observe(n_pages)
        eng = self.engine
        if eng.kv_pages:
            used = eng.kv_pages - len(eng._free_pages)
            if used > self.high_water:
                self.high_water = used

    def note_park(self, rid: int) -> None:
        self._churn[rid] = self._churn.get(rid, 0) + 1

    def note_resume(self, rid: int) -> None:
        self._churn[rid] = self._churn.get(rid, 0) + 1

    # ----------------------------------------------- shadow-draft hooks

    def _shadow_of(self, rid: int, prompt) -> ShadowPredictor:
        shadow = self._shadow.get(rid)
        if shadow is None:
            shadow = self._shadow[rid] = ShadowPredictor(
                SHADOW_ORDER, prompt, whole=False)
        return shadow

    def note_prefilled(self, rid: int, prompt, lo: int, hi: int) -> int:
        """A prefill chunk carrying ``prompt[lo:hi]`` has been
        dispatched: index those tokens while the device runs it (the
        engine's thread would only wait). Returns the tokens indexed."""
        return self._shadow_of(rid, prompt).index(lo, hi)

    def note_admitted(self, req) -> int:
        """The install: index what no chunk carried (a prefix-cache
        hit's head, a handed-over bundle's whole prompt, a
        block-diffusion prompt's remainder), after which the shadow
        holds the whole prompt. Returns the tokens indexed here."""
        self._churn.setdefault(req.id, 0)
        return self._shadow_of(req.id, req.prompt).finish()

    def observe_token(self, rid: int, tok: int) -> None:
        shadow = self._shadow.get(rid)
        if shadow is None:
            return
        hit = shadow.observe(tok)
        self.scored += 1
        if hit:
            self.accepted += 1
            self._run_cur[rid] = self._run_cur.get(rid, 0) + 1
        else:
            run = self._run_cur.pop(rid, 0)
            if run:
                self._close_run(run)

    def _close_run(self, n: int) -> None:
        _accept_run_len.observe(n)
        for i, bound in enumerate(_RUN_BOUNDS):
            if n <= bound:
                self._run_counts[i] += 1
                break
        else:
            self._run_counts[-1] += 1
        self._runs_closed += 1

    def note_retire(self, rid: int) -> None:
        run = self._run_cur.pop(rid, 0)
        if run:
            self._close_run(run)
        self._shadow.pop(rid, None)
        _park_churn.observe(self._churn.pop(rid, 0))

    def _run_percentile(self, q: float) -> int:
        if not self._runs_closed:
            return 0
        need = q * self._runs_closed
        seen = 0
        for i, count in enumerate(self._run_counts):
            seen += count
            if seen >= need:
                return (_RUN_BOUNDS[i] if i < len(_RUN_BOUNDS)
                        else _RUN_BOUNDS[-1] * 2)
        return _RUN_BOUNDS[-1] * 2

    # ------------------------------------------------------- sampling

    def on_step(self) -> None:
        """Called once per engine decode step; publishes a sample every
        ``sample_every`` steps."""
        self._steps += 1
        if self._steps % self.sample_every == 0:
            self.sample()

    def sample(self) -> dict:
        """Compute + publish one observation: gauges, the ``kv_pool``
        journal point (a Perfetto counter lane), and the snapshot the
        gateway aggregates."""
        eng = self.engine
        total = int(eng.kv_pages)
        free = len(eng._free_pages)
        used = total - free if total else 0
        if used > self.high_water:
            self.high_water = used
        active = sum(r is not None for r in eng._active)
        parked = len(eng._parked)
        store = getattr(eng, "_digest_store", None)
        if store is not None:
            # §31 satellite: the per-request digest store already
            # holds every chain digest — the sample reads lists, it
            # never rehashes token streams
            rids = [req.id for req in eng._active if req is not None]
            rids += [p.req.id for p in eng._parked]
            share = digest_share_stats(
                [store.pages(r) for r in rids])
        else:
            live = [
                list(req.prompt) + list(eng._emitted[s])
                for s, req in enumerate(eng._active)
                if req is not None
            ]
            live += [
                list(p.req.prompt) + list(p.emitted)
                for p in eng._parked
            ]
            share = page_share_stats(live, eng.page_size)
        rate = self.accepted / self.scored if self.scored else 0.0
        occupancy = (used / total if total
                     else (active / eng.slots if eng.slots else 0.0))
        cow_saved = int(getattr(eng, "cow_pages_saved", 0))
        # realized saved fraction: of the LOGICAL pages live requests
        # reference (unique leased + deduped entries), how many the
        # pool did not have to lease. The §29-predicted headroom
        # (shareable_frac) counts every family member, so realized
        # lands within family_size/(family_size-1) ~ 2x of it.
        logical = used + cow_saved
        spec_scored = int(getattr(eng, "spec_drafts_scored", 0))
        spec_rate = (
            int(getattr(eng, "spec_drafts_accepted", 0)) / spec_scored
            if spec_scored else 0.0
        )
        sample = {
            "free": free,
            "used": used,
            "total": total,
            "high_water": self.high_water,
            "occupancy": round(occupancy, 4),
            "active": active,
            "parked": parked,
            "total_pages": share["total_pages"],
            "unique_pages": share["unique_pages"],
            "shareable_pages": share["shareable_pages"],
            "shareable_frac": round(share["shareable_frac"], 4),
            "cow_multiplier": round(share["cow_multiplier"], 4),
            "families": share["families"],
            "largest_family": share["largest_family"],
            "accept_rate": round(rate, 4),
            "accepted": self.accepted,
            "scored": self.scored,
            "accept_run_p50": self._run_percentile(0.50),
            "accept_run_p95": self._run_percentile(0.95),
            # §31 live instruments (0 when COW/spec disabled)
            "cow_saved_pages": cow_saved,
            "cow_saved_frac": round(
                cow_saved / logical if logical else 0.0, 4),
            "cow_shared_total": int(
                getattr(eng, "cow_pages_shared_total", 0)),
            "cow_breaks": int(getattr(eng, "cow_breaks_total", 0)),
            "spec_steps": int(getattr(eng, "spec_steps_total", 0)),
            "spec_extra_tokens": int(
                getattr(eng, "spec_extra_tokens_total", 0)),
            "spec_accept_rate": round(spec_rate, 4),
            "spec_scored": spec_scored,
            "spec_collapsed": int(
                getattr(eng, "spec_collapsed_total", 0)),
            # what one cached token costs over all layers and stacks:
            # the model's cache tree decides (per-head keys and values,
            # or one latent row a layer)
            "cache_bytes_per_token": int(
                getattr(eng, "cache_bytes_per_token", 0)),
            # and what a slot keeps that no token position addresses (a
            # linear-attention state): 0 for a model of rows alone
            "state_bytes_per_slot": int(
                getattr(eng, "state_bytes_per_slot", 0)),
        }
        eid = eng.engine_id
        _pages_free.labels(eid).set(free)
        _pages_used.labels(eid).set(used)
        _pages_high_water.labels(eid).set(self.high_water)
        _shareable_frac_g.labels(eid).set(sample["shareable_frac"])
        _accept_rate_g.labels(eid).set(sample["accept_rate"])
        _cow_saved_g.labels(eid).set(cow_saved)
        _spec_rate_g.labels(eid).set(sample["spec_accept_rate"])
        get_journal().emit("kv_pool", **sample)
        with self._lock:
            self._last_sample = sample
        return sample

    def snapshot(self) -> dict:
        """Last published sample (possibly empty) — safe from any
        thread; the gateway health tick aggregates these per pool."""
        with self._lock:
            return dict(self._last_sample)
