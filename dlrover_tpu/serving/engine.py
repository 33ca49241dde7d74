"""Continuous-batching inference engine (the vLLM-backend analog).

Reference analog: the reference serves RLHF rollouts through vLLM
(atorch/atorch/rl/inference_backend/vllm_backend.py) — its core idea is
continuous batching: requests join and leave a fixed slot batch between
decode iterations, so the accelerator always steps a full batch instead
of waiting for the longest sequence. TPU-natively that becomes THREE
compiled programs total (prefill, slot-install, decode-step) over a
per-row-position cache (models/decode.py forward_cached with vector
``pos``). The cache is the model's own TREE of ``[L, B, max_len, ...]``
stacks (DESIGN.md §23.5: ``k`` and ``v`` per head, or one latent
stack); these programs carry, write and donate it whole and name none
of its stacks:

- **prefill**: [1, prefill_len] forward chunks filling a working cache
  row — long prompts loop the SAME compiled chunk (cache position
  carries across), so prompt length is bounded by max_len, not
  prefill_len. Only the final chunk is pad-tailed; trailing pads are
  overwritten just-in-time as decode advances, never attended.
- **install**: dynamic-update the prefilled row into the slot batch's
  cache at a traced slot index.
- **decode step**: one token for ALL slots at their own positions;
  per-slot sampling params are vectorized (temperature/top_k/top_p/
  eos_id as [slots] arrays), finished slots are host-side bookkeeping.

Static shapes everywhere: slot count, cache length and prefill length
are engine constants, so serving never recompiles after warmup.

**Chunked-prefill admission**: beside a live batch ``step()`` runs at
most one prefill chunk per decode STEP of the block it is about to run
(installs ride along), so a request that is decoding waits for at most
one chunk's compute per token it is about to receive — one chunk an
engine step at ``decode_block`` 1, up to eight at 8. The stall is
measured into the ``dlrover_tpu_engine_decode_stall_seconds`` histogram
(one observation per admitting step) and each completed admission emits
an ``engine_admit`` journal instant.

**Paged KV slots** (``kv_pages > 0``): a physical page pool
``[L, pages, page_size, kv_heads * head_dim]`` (the dense stacks'
trailing dims) backs the dense decode
cache. Admission reserves ``ceil((prompt+max_new)/page_size)`` pages —
capacity is a page ledger, not a dense-slot count — and a long-running
generation can be PARKED (its dense row scattered to its pages through
an ``_install``-style jitted helper) to free its slot for waiting
work, then resumed bit-identically (pages gathered back, host-side
seed/sample counters restored). Fair-share rotation falls out: the
scheduling quantum is one page of decoded tokens.

**Prefill/decode disaggregation**: ``prefill_begin``/``prefill_step``
run the chunk loop without touching decode slots and yield a
``KVBundle`` — page-granular (k, v) plus (pos, last) — that a DECODE
engine installs via ``submit_prefilled`` (the ``kv_handoff`` journal
instant). Bundles round-trip through host numpy, so they ship over the
shm ckpt channel / array_wire framing unchanged; in-process the
``device_put`` is the jnp.asarray at install.

``prefix_cache_entries > 0`` adds the vLLM automatic-prefix-caching
analog: prefilled KV rows are cached at chunk-aligned prompt prefixes
(LRU), and a new prompt resumes prefill from its longest cached aligned
prefix — shared system prompts (the RLHF rollout shape) skip nearly the
whole prefill. A hit changes which chunks run, never a program shape,
and a weight push invalidates the cache wholesale.

**Copy-on-write KV pages** (DESIGN.md §31, ``DLROVER_TPU_KV_COW``):
the page pool gains per-page refcounts and a sharing index keyed by
the §29 prefix CHAIN digests (one per-request digest store, shared
with the observatory — no double hashing). Admission dedups FULL
prompt-prefix pages against resident matching chains: a sharer's
page-table entries point at the owner's physical pages (incref), only
the remainder is leased fresh, so capacity counts *unique* pages.
Prefix pages are materialized into the pool at install and registered;
shared entries are never written (park scatters them to the scratch
page) — a write that WOULD land in a shared page (decode-dirty region
overlapping a shared entry) breaks the share copy-on-write style into
a fresh private page first. Park/resume and retire decref; a page
returns to the free list only at refcount zero.

**Speculative decoding** (§31, ``DLROVER_TPU_SPEC_DEPTH``): the §29
n-gram shadow predictor self-drafts k tokens (zero RNG, no draft
model) and the target model verifies them in ONE wide forward —
``_verify_block`` extends the §23 eos-in-block machinery with a
``[slots, k]`` token feed at per-slot positions. Position 0 always
feeds the exactly-sampled next token, so every verify step yields >= 2
tokens for a drafting row; position i is accepted while every fed
guess before it matched the true sample at the SAME draw index —
greedy token streams are bit-exact by construction. Depth k comes from
the measured §29 accept-run p50 prior; a request whose live acceptance
collapses falls back to k=1 (plain decode) for its lifetime.

**Block diffusion** (DESIGN.md §23.8, ``cfg.generation ==
'block_diffusion'``): a model that generates a BLOCK of
``cfg.block_length`` positions at a time. Prefill runs the prompt's
whole blocks under the block-causal mask (chunks of ``prefill_len``, a
multiple of the block length; the prefix cache at chunk boundaries as
ever) and the prompt's last ``len % block_length`` tokens open the first
generated block already unmasked. A decode call (``_denoise_blocks``)
runs whole blocks: ``denoising_steps`` passes over the block's positions
(each writes the block's rows and puts ``pos`` back, then unmasks the
still-masked positions of highest confidence) and one storing pass from
the final tokens that keeps the advance. What is masked is the engine's
own boolean a position. ``Result.unmask_steps`` says in which pass each
token was unmasked. Speculation, ``kv_pages``, park/resume and
handed-over bundles raise by name for such a model.

**The sampler under a gate**: every decode program takes per-row
sampling parameters as vectors (one program whatever mix of requests
holds the slots) and holds ``sample_logits`` under ``lax.cond`` on a
traced fact of the call, ``any(active & (temperature > 0))``: a call
whose LIVE rows are all greedy takes ``argmax(logits)`` and skips the
sort of ``[slots, vocab]``, the softmax and the draws; one with any live
sampling row runs the sampler whole, so sampled streams are what they
were and greedy rows beside them too. ``active`` is in the predicate
because an empty slot's default temperature is 1.0. The ``decode_block``
span's ``sampling_rows`` is the host's count of the same rows.

**State beside rows** (DESIGN.md §23.5): a model's cache tree may hold,
beside rows ``[L, B, len, ...]``, STATE that no token position addresses
(``models.decode.cache_state``: a linear-attention layer's matrix).
Everywhere this engine keeps a row from advancing by putting ``pos``
back (an idle slot, a row past its eos or frozen past its budget inside
a block, the pad tail of a prompt's final chunk) it also tells the
cached forward how many of the call's tokens are real, a row, and such a
model takes in no other. ``_install``, the working row and the prefix
cache carry state with the rows (an entry holds the state AT its chunk
boundary); ``kv_pages``, speculation and bundles, which assume a
position axis, raise by name. A windowed layer's RING
(``models/decode.py``: the last ``window`` keys of a row, position ``p``
in slot ``p % window``) is state in this sense and is kept there: only
the model may index it by position, a prefix-cache entry holds it as it
stood AT its boundary, and a pad, told to the model as not real, is
never written into it.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
import weakref
from collections import deque
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common import envspec
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models.decode import (
    cache_counter_fields,
    cache_stacks,
    cache_state,
    forward_cached,
    init_cache,
    sample_logits,
    weights_at_rest,
    zero_counters,
)
from dlrover_tpu.models.transformer import TransformerConfig
from dlrover_tpu.serving.observatory import (
    PrefixDigestStore,
    ServingObservatory,
)
from dlrover_tpu.telemetry.journal import HotSpan, get_journal, hot_span
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

# engine instances in one process share the metrics registry; gauges
# are disambiguated by a per-process engine id label
_ENGINE_IDS = itertools.count()

_queue_wait_seconds = registry().histogram(
    "dlrover_tpu_engine_queue_wait_seconds",
    "submit -> the admission pipeline taking the request up (the "
    "`kv_install` span's `queue_wait_s`): the engine's own queue, which "
    "the gateway's queue histogram ends before",
    label_names=("engine",),
    buckets=(0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0),
)
_decode_stall_seconds = registry().histogram(
    "dlrover_tpu_engine_decode_stall_seconds",
    "admission work (prefill chunk / install) run between decode "
    "steps while slots were actively decoding",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
             2.5, 5.0),
)


# gauges for a scrape of what a model's cache counters say of the newest
# decode call, keyed by the counter's name (the model names its
# counters, DESIGN.md §23.5; metric names are literals, which the
# metric-name lint holds the package to, so a counter that wants a
# gauge is listed here; a model that counts nothing sets none)
_COUNTER_GAUGES = {
    "expert_tokens": registry().gauge(
        "dlrover_tpu_engine_expert_tokens",
        "token-expert assignments that landed on held experts in the "
        "engine's newest decode call, summed over its layers and steps "
        "(every row of the slot batch routes, idle slots too)",
        label_names=("engine",)),
    "expert_load_max_over_mean": registry().gauge(
        "dlrover_tpu_engine_expert_load_max_over_mean",
        "the busiest held expert's assignments in the newest decode "
        "call over the mean of all held experts (1.0: even)",
        label_names=("engine",)),
    "sparse_keys_share": registry().gauge(
        "dlrover_tpu_engine_sparse_keys_share",
        "keys the selection of a block-sparse model chose in the engine's "
        "newest decode call over the keys whose scores its attention "
        "computed (1.0: it read what it selected and no more)",
        label_names=("engine",)),
    "state_bytes_per_slot": registry().gauge(
        "dlrover_tpu_engine_state_bytes_per_slot",
        "bytes of cache a slot holds that no token position addresses "
        "(a linear-attention layer's state, a state-space layer's state "
        "and convolution window), whatever its context; set once, by an "
        "engine whose model keeps such state",
        label_names=("engine",)),
    "experts_hit_share": registry().gauge(
        "dlrover_tpu_engine_experts_hit_share",
        "held experts that took at least one assignment in the engine's "
        "newest decode call over the held experts of its expert layers "
        "and steps (each one hit is one expert's weights read; 1.0: "
        "every step read them all)",
        label_names=("engine",)),
}
_decoding_slots = registry().gauge(
    "dlrover_tpu_engine_decoding_slots",
    "slots that took part in the engine's newest decode call (0 when "
    "the newest step made none), per engine",
    label_names=("engine",),
)
_frozen_row_share = registry().gauge(
    "dlrover_tpu_engine_frozen_row_share",
    "row-steps of the engine's newest autoregressive decode call spent "
    "by rows already past their budget, over its rows x steps (what "
    "whole blocks cost: a row that ends inside a block idles to its "
    "end); unset for a block-diffusion model",
    label_names=("engine",),
)
_tokens_per_pass = registry().gauge(
    "dlrover_tpu_engine_tokens_per_pass",
    "tokens the engine's newest block-diffusion decode call delivered "
    "over its rows x forward passes (4 tokens a row in 5 passes: 0.8); "
    "unset for an autoregressive model, whose pass yields one token",
    label_names=("engine",),
)
_kv_parked_total = registry().counter(
    "dlrover_tpu_engine_kv_parked_total",
    "active generations parked to their KV pages to free a decode slot",
)
_kv_handoffs_total = registry().counter(
    "dlrover_tpu_engine_kv_handoffs_total",
    "prefilled KV bundles installed from a prefill engine",
)
_prefix_cache_hits_total = registry().counter(
    "dlrover_tpu_engine_prefix_cache_hits_total",
    "prefill runs resumed from a cached aligned prefix",
)
_prefix_cache_queries_total = registry().counter(
    "dlrover_tpu_engine_prefix_cache_queries_total",
    "prefill runs that probed the prefix cache",
)
_prefix_cache_entries = registry().gauge(
    "dlrover_tpu_engine_prefix_cache_entries",
    "prefilled KV rows currently pinned in the prefix LRU, per engine",
    label_names=("engine",),
)
_kv_cow_shared_total = registry().counter(
    "dlrover_tpu_engine_kv_cow_shared_total",
    "page-table entries deduped onto a resident shared page at "
    "admission (copy-on-write prefix sharing)",
)
_kv_cow_breaks_total = registry().counter(
    "dlrover_tpu_engine_kv_cow_breaks_total",
    "copy-on-write breaks: a write would have landed in a shared "
    "page, so the entry was re-pointed at a fresh private page",
)
_spec_verify_steps_total = registry().counter(
    "dlrover_tpu_spec_verify_steps_total",
    "speculative verify dispatches (one wide forward verifying a "
    "self-drafted token block)",
)
_spec_extra_tokens_total = registry().counter(
    "dlrover_tpu_spec_extra_tokens_total",
    "tokens emitted by verify steps beyond the one-per-slot a plain "
    "decode step would have produced",
)
_spec_collapsed_total = registry().counter(
    "dlrover_tpu_spec_collapsed_total",
    "requests whose live draft acceptance collapsed and fell back to "
    "k=1 plain decode for their remaining lifetime",
)

# engines register here so the test suite can assert the page-ledger
# conservation invariant after every engine-touching test
_LIVE_ENGINES: "weakref.WeakSet[InferenceEngine]" = weakref.WeakSet()

# adaptive-depth collapse policy (§31): after this many scored REAL
# draft tokens, a live acceptance below the floor drops the request to
# k=1 for good — worst case then ~ plain decode, not a 2x flop tax
_SPEC_COLLAPSE_MIN_SCORED = 16
_SPEC_COLLAPSE_RATE = 0.2

# Canonical low-precision numerics for the two programs that WRITE
# decode KV. The wide verify forward and the narrow block scan are
# DIFFERENT XLA programs; with excess precision allowed (the default),
# fusion keeps different subsets of their bf16 intermediates in f32,
# so ~1% of KV writes land one bf16 ulp apart between the programs —
# enough to flip a greedy argmax hundreds of tokens later and break
# the §31 spec-on/off token-identity pin. Forcing every intermediate
# to its stated dtype makes both programs' KV bit-identical to the
# eager op-by-op semantics, hence to each other, at ~zero cost on the
# decode hot path (tests/test_serving_speed.py pins this end to end).
_CANONICAL_NUMERICS = {"xla_allow_excess_precision": False}


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 64
    eos_id: int | None = None
    # per-request determinism: with a seed, the continuation depends
    # only on (params, prompt, sampling params, seed) — identical
    # whatever else shares the batch. None -> engine-generated seed.
    seed: int | None = None


@dataclasses.dataclass
class Request:
    id: int
    prompt: list[int]
    params: SamplingParams
    # streaming: called as on_token(request_id, token) for each ACCEPTED
    # token, in order, from step()'s host loop. With decode_block > 1
    # tokens arrive in bursts of up to block size — streaming-latency-
    # sensitive callers trade throughput with decode_block=1.
    on_token: Any = None
    # a prefill-pool product to install instead of running prefill here
    bundle: Any = None
    # trace:span context (§27) of the gateway request this serves; this
    # engine's admit/handoff journal events attach under it
    sctx: str = ""


@dataclasses.dataclass
class Result:
    id: int
    prompt: list[int]
    tokens: list[int]          # generated continuation (no prompt)
    finish_reason: str         # "eos" | "length"
    # a block-diffusion model: for each token the denoising pass (0-based,
    # within its block) that unmasked it: what was served cannot be
    # replayed without it. Empty for an autoregressive model
    unmask_steps: list[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class KVBundle:
    """Prefilled KV handed from a prefill engine to a decode engine.

    Page-granular and host-resident: ``stacks`` maps each stack of the
    model's cache tree (``k`` and ``v``, or one ``latent``) to a
    ``[L, n_pages, page_size, ...]`` numpy array
    covering only the pages the prompt actually filled, so the handoff
    ships ``ceil(prompt/page)`` pages, never a full max_len row. Plain
    numpy means the same bundle travels in-process (jnp.asarray at
    install = the explicit device_put) or across processes over the
    array_wire / shm ckpt framing.
    """

    stacks: dict
    pos: int                   # true prompt length
    last: Any                  # [vocab] float32 logits of the last token
    page_size: int
    prefix_key: tuple          # final-aligned-boundary prefix key
    # trace:span context (§27) carried with the KV across the process
    # boundary so the decode side's install journals into the same tree
    sctx: str = ""


@dataclasses.dataclass
class _PrefillRun:
    """One in-flight chunked prefill (admission or prefill-pool)."""

    prompt: list[int]
    row: Any                   # the working row: a [L, 1, max_len, ...] cache tree
    last: Any
    next_lo: int               # next chunk start offset
    start: int                 # where prefill resumed (prefix-cache hit)
    chunks: int = 0
    work_s: float = 0.0
    done: bool = False
    # whose prompt this is, for the spans (-1: the prefill pool's)
    request: int = -1
    sctx: str = ""
    # how many of the prompt's tokens prefill runs (-1: all of them; a
    # block-diffusion model: the whole blocks, the rest opens the first
    # generated block)
    upto: int = -1

    def __post_init__(self):
        if self.upto < 0:
            self.upto = len(self.prompt)


@dataclasses.dataclass
class _PendingAdmit:
    """A request between queue and slot: its prefill run + page lease."""

    req: Request
    run: _PrefillRun
    pages: list[int]
    kind: str = "cold"         # cold | hit | handoff
    # table indices (into `pages`) attached to SHARED physical pages
    # at admission — already incref'd, never scattered to
    shared: set = dataclasses.field(default_factory=set)
    # the request's time inside the engine, for its `kv_install` span:
    # when `_start_admission` took it up (monotonic), how long it had
    # queued by then, and the host seconds that call cost
    taken: float = 0.0
    queue_wait_s: float = 0.0
    start_s: float = 0.0


@dataclasses.dataclass
class _Parked:
    """A generation evicted from its slot: truth lives in its pages
    plus this host-side continuation state."""

    req: Request
    pages: list[int]
    pos: int
    last: Any                  # [vocab] device array
    seed: int
    sampled: int
    emitted: list[int]
    shared: set = dataclasses.field(default_factory=set)


class InferenceEngine:
    """Fixed-slot continuous batching over one model.

    Usage::

        eng = InferenceEngine(params, cfg, slots=8, max_len=256)
        rid = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=32))
        results = eng.run()          # drain queue + active slots

    Where the weights rest (DESIGN.md §23.6): the engine keeps
    ``models.decode.weights_at_rest(params, cfg)`` and not the tree it
    was handed, at construction and at every push through ``params``:
    the leaves its products read in ``cfg.dtype``, converted once (a
    leaf already there is kept as the same array, not copied), the norm
    leaves and the capacity-routed experts as they came. No program of
    the engine converts those leaves again, and a caller that drops its
    own tree leaves one copy of the model on the device, in ``cfg.dtype``.
    """

    def __init__(self, params: Any, cfg: TransformerConfig, *,
                 slots: int = 8, max_len: int = 0,
                 prefill_len: int = 0, decode_block: int = 1,
                 prefix_cache_entries: int = 0,
                 kv_pages: int = 0, page_size: int = 0):
        # held as the programs read them, and only so (class docstring)
        self._params = weights_at_rest(params, cfg)
        self.cfg = cfg
        self.slots = slots
        self.engine_id = f"eng{next(_ENGINE_IDS)}"
        self.max_len = max_len or cfg.max_seq_len
        # default chunk: the largest divisor of max_len <= 64 (a real
        # divisor search — gcd would only extract the power-of-two
        # factor and degrade to per-token prefill for odd max_len). The
        # divisibility invariant is what makes chunked prefill safe: a
        # final pad-tailed chunk then never extends past max_len, where
        # XLA's clamped dynamic_update_slice would silently overwrite
        # EARLIER cache positions with misaligned data.
        if not prefill_len:
            prefill_len = next(
                d for d in range(min(64, self.max_len), 0, -1)
                if self.max_len % d == 0
            )
        self.prefill_len = prefill_len
        if self.prefill_len > self.max_len:
            raise ValueError("prefill_len > max_len")
        if self.max_len % self.prefill_len:
            raise ValueError(
                f"prefill_len {self.prefill_len} must divide max_len "
                f"{self.max_len} (a clamped final chunk write would "
                "corrupt earlier cache rows)"
            )
        # decode_block > 1: run up to that many decode iterations inside
        # ONE compiled scan before syncing tokens to the host — the
        # per-token host round trip (sync + dispatch) otherwise bounds
        # throughput on high-RTT hosts. A call is a whole block while
        # any active row has that much left (`_block_size`); budget and
        # eos are both observed INSIDE the compiled block, per slot (a
        # row past its ``remaining`` budget, or one that sampled its
        # eos, stops advancing its cache position and the host reads
        # none of its later tokens), so neither a row in its last
        # tokens nor an eos-bearing request shortens its batchmates'
        # call.
        self.decode_block = max(1, decode_block)
        # a block-diffusion model (module docstring): what the engine
        # was not made to do with one raises here, by name
        self._diffusion = cfg.generation == "block_diffusion"
        if self._diffusion:
            if self.prefill_len % cfg.block_length:
                raise ValueError(
                    f"prefill_len {self.prefill_len} must be a multiple "
                    f"of the model's block_length {cfg.block_length}: a "
                    "chunk boundary inside a block would cut the block's "
                    "own keys off its queries")
            if kv_pages > 0:
                raise NotImplementedError(
                    "kv_pages > 0 (the paged store, park/resume, "
                    "copy-on-write pages) with a block-diffusion model: a "
                    "parked row would have to carry its open block's "
                    "masked state, which no page holds (run it with "
                    "kv_pages=0)")

        # paged KV slots: physical page pool + per-slot page lease.
        # Capacity is a PAGE ledger — a request holds
        # ceil((prompt+max_new)/page_size) pages from admission to
        # retire — so short requests no longer cost a whole dense
        # slot's worth of memory headroom, and a long generation can be
        # parked to its pages (freeing the slot) and resumed
        # bit-identically. Page 0 is a scratch page: unused page-table
        # entries point at it, so the scatter/gather helpers stay
        # mask-free (garbage beyond a request's allocation is never
        # attended — positions past pos sit under the causal mask).
        self.page_size = page_size or self.prefill_len
        if self.max_len % self.page_size:
            raise ValueError(
                f"page_size {self.page_size} must divide max_len "
                f"{self.max_len}"
            )
        self.kv_pages = int(kv_pages)
        self.pages_per_slot = self.max_len // self.page_size
        self._paging = self.kv_pages > 0
        # what the model's cache tree holds (DESIGN.md §23.5), as shapes:
        # rows and, for some, state that no token position addresses
        tree = jax.eval_shape(lambda: init_cache(cfg, slots, self.max_len))
        self._stateful = bool(cache_state(tree))
        if self._paging and self._stateful:
            raise NotImplementedError(
                "kv_pages > 0 (the paged store, park/resume, copy-on-write "
                "pages) with a model that carries state (or a ring: a "
                "windowed layer's rows): a page holds token-addressed "
                "rows, and a parked row's state or ring has no page (run "
                "it with kv_pages=0)")
        if self._paging and cfg.attn_kind != "heads":
            raise NotImplementedError(
                f"kv_pages > 0 with attn_kind {cfg.attn_kind!r}: the paged "
                "store, park/resume and copy-on-write hold per-head [kv_heads "
                "* head_dim] pages; a latent cache has none (run it with "
                "kv_pages=0)")
        if self._paging:
            # a page holds `page_size` positions of a row as the stacks
            # hold them: their trailing dims, whatever those are
            rows = tree["k"]
            pool_shape = (rows.shape[0], self.kv_pages + 1, self.page_size,
                          *rows.shape[3:])
            self._kpool = jnp.zeros(pool_shape, rows.dtype)
            self._vpool = jnp.zeros(pool_shape, rows.dtype)
            self._free_pages: list[int] = list(
                range(1, self.kv_pages + 1))
        else:
            self._kpool = self._vpool = None
            self._free_pages = []
        # copy-on-write page sharing (§31): refcount per LEASED
        # physical page (private pages sit at 1), the sharing index
        # chain-digest -> resident physical page, and its reverse map
        # (for unregistering at free). All maintenance is host-side.
        self._cow = self._paging and envspec.get_bool(EnvKey.KV_COW)
        self._page_refs: dict[int, int] = {}
        self._share_index: dict[bytes, int] = {}
        self._page_digest: dict[int, bytes] = {}
        self.cow_pages_shared_total = 0
        self.cow_breaks_total = 0

        # prefix caching (the vLLM automatic-prefix-caching analog,
        # reference atorch/rl/inference_backend/vllm_backend.py): an LRU
        # of prefilled working rows keyed by CHUNK-ALIGNED token
        # prefixes. A new prompt resumes prefill from its longest cached
        # aligned prefix — for RLHF rollouts sharing a system prompt
        # that removes nearly the whole prefill. TPU-static: entries are
        # full [L, 1, max_len, ...] KV rows (the same shape the working
        # row already has), so a hit changes WHICH chunks run, never a
        # program shape. Each entry pins ~2 * n_layers * max_len *
        # kv_heads * head_dim * dtype bytes of device memory — size
        # `prefix_cache_entries` (0 = off) to the HBM you can spare.
        self.prefix_cache_entries = prefix_cache_entries
        self._prefix_cache: dict[tuple, tuple] = {}
        # key length -> number of stored keys of that length: lookups
        # probe only lengths that exist, so a long-prompt miss costs
        # O(stored lengths) hashes instead of rebuilding and hashing
        # every aligned prefix of the prompt (O(n^2/P))
        self._prefix_lens: dict[int, int] = {}
        self.prefix_cache_hits = 0
        self.prefix_cache_queries = 0

        self._queue: deque[Request] = deque()
        self._ids = itertools.count()
        self._submit_time: dict[int, float] = {}
        # host-side slot bookkeeping; None = free
        self._active: list[Request | None] = [None] * slots
        self._emitted: list[list[int]] = [[] for _ in range(slots)]
        self._slot_pages: list[list[int] | None] = [None] * slots
        self._slot_shared: list[set | None] = [None] * slots
        self._since_install = [0] * slots
        # block diffusion: the prompt's remainder that opens a slot's
        # first generated block (None once that block ran), and the
        # denoising pass that unmasked each emitted token
        self._opening: list[list[int] | None] = [None] * slots
        self._unmask: list[list[int]] = [[] for _ in range(slots)]
        self._results: list[Result] = []
        # admission state machine: at most one pending chunked prefill
        # plus a FIFO of parked generations awaiting a slot
        self._pending: _PendingAdmit | None = None
        # prefill chunks run, ever: `_step` reads the difference across
        # its admission for the `engine_step` span's `prefill_chunks`
        self._chunks_run = 0
        self._parked: deque[_Parked] = deque()
        self.kv_parked_total = 0
        # sampling tensors are invalidated only on admit/park/retire —
        # steady-state decode re-uses the uploaded arrays instead of
        # rebuilding + re-uploading [slots] vectors every step
        self._samp_cache: tuple | None = None

        # measure-only serving observatory (DESIGN.md §29): page-pool
        # pressure, prefix-share headroom, draft-acceptance shadowing.
        # Host-side bookkeeping only — the identity test pins that the
        # token stream is bit-identical with it on or off.
        self._obs: ServingObservatory | None = None
        if envspec.get_bool(EnvKey.SERVING_OBSERVATORY):
            self._obs = ServingObservatory(
                self,
                sample_every=envspec.get_int(
                    EnvKey.OBSERVATORY_SAMPLE_EVERY, 32),
            )

        # one per-request digest store feeds BOTH the COW sharing
        # index and the observatory's prefix-share sample (§31
        # satellite: chain digests are computed once, incrementally at
        # page boundaries — the sample never rehashes token lists)
        self._digest_store: PrefixDigestStore | None = None
        if self._cow or self._obs is not None:
            self._digest_store = PrefixDigestStore(self.page_size)

        # speculative decoding (§31): the drafter and the run-length
        # depth prior live in the observatory, so speculation requires
        # it; depth < 2 or a missing observatory means plain decode
        self.spec_depth = max(0, envspec.get_int(EnvKey.SPEC_DEPTH, 0))
        if self._diffusion and self.spec_depth >= 2:
            raise NotImplementedError(
                "speculation (spec_depth >= 2) with a block-diffusion "
                "model: `_verify_block` accepts a drafted run of NEXT "
                "tokens, and a denoising pass has no next token")
        if self._stateful and self.spec_depth >= 2:
            raise NotImplementedError(
                "speculation (spec_depth >= 2) with a model that carries "
                "state (or a ring: a windowed layer's rows): "
                "`_verify_block` takes a rejected draft back by putting "
                "the position back, and a state has already folded the "
                "draft in, a ring has lost the keys the draft lay over")
        self._spec = self.spec_depth >= 2 and self._obs is not None
        # rid -> [accepted, scored, collapsed] live draft accounting
        self._spec_acc: dict[int, list[int]] = {}
        self.spec_steps_total = 0
        self.spec_extra_tokens_total = 0
        self.spec_drafts_accepted = 0
        self.spec_drafts_scored = 0
        self.spec_collapsed_total = 0

        # The cache is the MODEL's tree (DESIGN.md §23.5): `pos`, stacks
        # laid out [L, B, max_len, ...] under names this engine never
        # reads (`cache_stacks`), and counters the model may keep. The
        # stacks are never copied whole (§23.1): every program that
        # returns the tree DONATES it and updates it in place. So
        # nobody keeps a reference to `_cache` (or `_last`) across a
        # call that returns them; the engine rebinds both from the
        # call's outputs.
        self._cache = init_cache(cfg, slots, self.max_len)
        self._cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self._last = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
        # what a token costs to keep, over all layers and ROWS (a stack
        # shorter than max_len, compressed keys, costs its share), and
        # what a slot keeps whatever its context
        self.cache_bytes_per_token = sum(
            a.nbytes for a in cache_stacks(self._cache).values()
        ) // (slots * self.max_len)
        self.state_bytes_per_slot = sum(
            a.nbytes for a in jax.tree.leaves(cache_state(self._cache))
        ) // slots
        if self._stateful:
            _COUNTER_GAUGES["state_bytes_per_slot"].labels(
                self.engine_id).set(self.state_bytes_per_slot)
        # what a working row carries beside rows, state and position
        self._row_extras = {
            "counters": jax.tree.map(jnp.zeros_like, self._cache["counters"])
        } if "counters" in self._cache else {}
        # per-slot sampling randomness: a seed per REQUEST + a count of
        # tokens sampled so far — the per-draw key is derived from both,
        # so a request's stream never depends on batch composition
        self._seeds = np.zeros((slots,), np.uint32)
        self._sampled = np.zeros((slots,), np.int64)
        self._seed_gen = np.random.default_rng(0)

        # --- compiled programs ---------------------------------------
        stateful = self._stateful
        def _prefill_chunk(params, tokens, row, true_len):
            # one prefill_len chunk into a [1, max_len] working row;
            # long prompts loop this program (the row's pos carries
            # across chunks, so only the FINAL chunk may be pad-tailed —
            # a mid-sequence pad would sit under later queries' causal
            # mask; a model that keeps state is told how many of the
            # chunk's tokens are real, and takes in no pad). Returns the
            # last REAL token's logits of the chunk and what the model
            # counted in it.
            logits, row = forward_cached(params, tokens,
                                         zero_counters(row), cfg,
                                         real=true_len)
            return row, logits[0, true_len - 1], cache_counter_fields(row)

        self._prefill_chunk = jax.jit(_prefill_chunk)

        def _install(cache, last_all, row, last_row, slot, true_len):
            # write the prefilled row into slot `slot` of every stack:
            # rows and state alike are indexed by slot on their second axis
            def put(stack, mine):
                return lax.dynamic_update_index_in_dim(
                    stack, mine[:, 0], slot, axis=1)

            stacks = {name: put(stack, row[name])
                      for name, stack in cache_stacks(cache).items()}
            cache = {**cache, **stacks,
                     "pos": cache["pos"].at[slot].set(true_len)}
            if cache_state(cache):
                cache["state"] = jax.tree.map(put, cache_state(cache),
                                              cache_state(row))
            return cache, last_all.at[slot].set(last_row)

        self._install = jax.jit(_install, donate_argnums=(0, 1))

        if self._paging:
            L = cfg.n_layers
            pps, ps = self.pages_per_slot, self.page_size

            def _park_out(cache_k, cache_v, kpool, vpool, slot, table):
                # scatter slot `slot`'s dense row into its pages
                # (`table`: [pages_per_slot] physical ids, unused
                # entries -> scratch page 0)
                row_k = lax.dynamic_index_in_dim(
                    cache_k, slot, axis=1, keepdims=False)
                row_v = lax.dynamic_index_in_dim(
                    cache_v, slot, axis=1, keepdims=False)
                shape = (L, pps, ps) + row_k.shape[2:]
                kpool = kpool.at[:, table].set(row_k.reshape(shape))
                vpool = vpool.at[:, table].set(row_v.reshape(shape))
                return kpool, vpool

            self._park_out = jax.jit(_park_out)

            def _resume_install(cache_k, cache_v, pos_all, last_all,
                                kpool, vpool, table, slot, pos,
                                last_row):
                # gather pages back into a dense row and install it —
                # the resume twin of `_install`
                shape = (L, pps * ps) + kpool.shape[3:]
                row_k = kpool[:, table].reshape(shape)
                row_v = vpool[:, table].reshape(shape)
                cache_k = lax.dynamic_update_index_in_dim(
                    cache_k, row_k, slot, axis=1)
                cache_v = lax.dynamic_update_index_in_dim(
                    cache_v, row_v, slot, axis=1)
                pos_all = pos_all.at[slot].set(pos)
                last_all = last_all.at[slot].set(last_row)
                return cache_k, cache_v, pos_all, last_all

            self._resume_install = jax.jit(
                _resume_install, donate_argnums=(0, 1, 2, 3))

        def _row_keys(seeds, counts):
            # per-row key = f(request seed, index of this draw): pure
            # per-request randomness, batch-composition-independent
            return jax.vmap(
                lambda s, c: jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), s), c
                )
            )(seeds, counts)

        def _draw(sampling, logits, seeds, counts, temperature, top_k,
                  top_p):
            # the sampler under its gate (module docstring): `sampling`
            # is a traced fact of the call. A greedy row's token is the
            # same on both branches: masking entries below the maximum
            # never moves the first index of the maximum
            return lax.cond(
                sampling,
                lambda: sample_logits(
                    logits, _row_keys(seeds, counts), temperature,
                    top_k, top_p),
                lambda: jnp.argmax(logits, -1).astype(jnp.int32))

        def _step_block(params, cache, last, seeds, counts,
                        temperature, top_k, top_p, active, eos_ids,
                        remaining, n_steps):
            # per-row sampling params as VECTORS: one compiled program
            # regardless of the mix of requests in the batch. eos_ids
            # [slots] (-1 = none): a row that samples its eos keeps
            # emitting eos and stops advancing — the host retires it
            # after the block, so the batchmates never drop to
            # token-at-a-time decode. remaining [slots]: tokens each
            # row's budget still allows; a row stops advancing at step
            # ``remaining`` the same way (its pos never passes prompt +
            # max_new_tokens, so neither its page lease nor max_len),
            # and the host reads none of its tokens from there on.
            # `active` is in the predicate because an EMPTY slot's
            # default temperature is 1.0 (`_sampling_tensors`).
            sampling = jnp.any(active & (temperature > 0))

            def body(carry, i):
                cache, last, done = carry
                nxt = _draw(sampling, last, seeds, counts + i,
                            temperature, top_k, top_p)
                nxt = jnp.where(done, jnp.maximum(eos_ids, 0), nxt)
                hit = (eos_ids >= 0) & (nxt == eos_ids)
                # inactive/finished rows must not advance (their pos
                # would creep past max_len and clamp the next install's
                # attention): their position is put back. A model that
                # keeps state has to be told BEFORE the forward that
                # their token is not real; for a model of rows alone the
                # mask is made where it always was, so that its program
                # stays what it was to the letter
                def advancing():
                    return active & ~done & (i < remaining)

                run = advancing() if stateful else None
                logits, new = forward_cached(
                    params, nxt[:, None], cache, cfg, real=run
                )
                if run is None:
                    run = advancing()
                new["pos"] = jnp.where(run, new["pos"], cache["pos"])
                return (new, logits[:, 0], done | hit), nxt

            done0 = jnp.zeros(active.shape, bool)
            (cache, last, _), toks = lax.scan(
                body, (zero_counters(cache), last, done0),
                jnp.arange(n_steps)
            )
            return toks, cache, last, cache_counter_fields(cache)

        self._step_block = jax.jit(
            _step_block, static_argnames=("n_steps",),
            donate_argnums=(1, 2),
            compiler_options=_CANONICAL_NUMERICS,
        )

        def _verify_block(params, cache, last, seeds, counts,
                          temperature, top_k, top_p, active, eos_ids,
                          guesses):
            # speculative verify (§31): ONE wide forward checks a
            # whole drafted block. ``guesses`` is [slots, n] int32 —
            # column 0 doubles as the per-slot spec flag (>= 0: this
            # row drafted; -1: plain row, advances exactly one token).
            # Position 0 feeds the EXACTLY-sampled next token (same
            # draw index as a plain step), positions 1..n-1 feed the
            # drafter's guesses; true token i is sampled from the wide
            # logits at i-1 with the plain path's draw index i, so
            # accepted streams are bit-exact by construction. Only x0
            # plus the MATCHED run is accepted — never the correction
            # token after a miss: its position was fed the wrong
            # guess, so its KV write and successor logits are stale.
            # Nothing is lost: new_last is the very distribution that
            # produces it, so the next dispatch's x0 re-derives the
            # correction bit-identically AND writes its KV. An eos
            # inside the accepted window truncates it, §23-style.
            n = guesses.shape[1]
            sampling = jnp.any(active & (temperature > 0))
            x0 = _draw(sampling, last, seeds, counts, temperature,
                       top_k, top_p)
            fed = jnp.concatenate(
                [x0[:, None], jnp.maximum(guesses[:, 1:], 0)], axis=1
            )
            pos = cache["pos"]
            logits, cache = forward_cached(params, fed,
                                           zero_counters(cache), cfg)
            toks = [x0]
            for i in range(1, n):
                toks.append(_draw(
                    sampling, logits[:, i - 1], seeds, counts + i,
                    temperature, top_k, top_p))
            toks = jnp.stack(toks, axis=1)              # [slots, n]
            match = (guesses[:, 1:] == toks[:, 1:]).astype(jnp.int32)
            run = jnp.cumprod(match, axis=1).sum(axis=1)
            spec_on = guesses[:, 0] >= 0
            acc = jnp.where(spec_on, 1 + run, 1)
            hit = (eos_ids[:, None] >= 0) & (toks == eos_ids[:, None])
            idx = jnp.arange(n)[None, :]
            eos_at = jnp.min(
                jnp.where(hit & (idx < acc[:, None]), idx, n), axis=1
            )
            acc = jnp.minimum(acc, eos_at + 1)
            acc = jnp.where(active, acc, 0)
            sel = jnp.maximum(acc - 1, 0)
            new_last = jax.vmap(lambda row, i: row[i])(logits, sel)
            new_last = jnp.where(active[:, None], new_last, last)
            cache["pos"] = jnp.where(active, pos + acc, pos)
            return (toks, cache, new_last, acc,
                    cache_counter_fields(cache))

        self._verify_block = jax.jit(
            _verify_block, donate_argnums=(1, 2),
            compiler_options=_CANONICAL_NUMERICS,
        )

        def _denoise_blocks(params, cache, tokens0, masked0, seeds,
                            counts, temperature, top_k, top_p, active,
                            eos_ids, n_blocks):
            # block diffusion (module docstring): ``n_blocks`` whole
            # blocks for ALL slots at their own (block-aligned)
            # positions. ``tokens0`` / ``masked0`` [slots, B] open the
            # call's FIRST block (a fresh row's prompt remainder sits
            # there unmasked); later blocks open all masked. Returns
            # the blocks' final tokens and the pass that unmasked each
            # position, [n_blocks, slots, B] (-1: never masked).
            B, T = cfg.block_length, cfg.denoising_steps
            per_pass = B // T
            sampling = jnp.any(active & (temperature > 0))
            at = jnp.arange(B, dtype=jnp.int32)

            def unmask(logits, tokens, masked, step_of, s, draw):
                # candidates, their confidences, the choice
                flat = logits.reshape(-1, logits.shape[-1])

                def sampled(_):
                    keys = jax.vmap(lambda k: jax.vmap(
                        lambda j: jax.random.fold_in(k, j))(at))(
                            _row_keys(seeds, counts + draw))
                    return sample_logits(
                        flat, keys.reshape(flat.shape[0], -1),
                        jnp.repeat(temperature, B), jnp.repeat(top_k, B),
                        jnp.repeat(top_p, B))

                # all-greedy calls (a traced fact) skip the sampler's
                # sort over [slots x B, vocab]
                cand = lax.cond(
                    sampling, sampled,
                    lambda _: jnp.argmax(flat, -1).astype(jnp.int32),
                    None).reshape(tokens.shape)
                conf = jnp.exp(
                    jnp.take_along_axis(logits, cand[..., None], -1)[..., 0]
                    - jax.nn.logsumexp(logits, axis=-1))
                for _ in range(per_pass):
                    pick = jnp.argmax(jnp.where(masked, conf, -1.0), axis=1)
                    chosen = ((at[None] == pick[:, None])
                              & jnp.take_along_axis(
                                  masked, pick[:, None], 1))
                    tokens = jnp.where(chosen, cand, tokens)
                    step_of = jnp.where(chosen, s, step_of)
                    masked = masked & ~chosen
                return tokens, masked, step_of

            def one_block(carry, b):
                cache, done = carry
                first = b == 0
                tokens = jnp.where(first, tokens0,
                                   jnp.int32(cfg.mask_token_id))
                masked = jnp.where(first, masked0, True)
                generated = masked

                def one_pass(s, state):
                    cache, tokens, masked, step_of = state
                    logits, new = forward_cached(params, tokens, cache,
                                                 cfg)
                    # the pass wrote the block's rows; they are not
                    # final, so the position stays
                    new["pos"] = cache["pos"]
                    with jax.named_scope("unmask"):
                        tokens, masked, step_of = unmask(
                            logits, tokens, masked, step_of, s, b * T + s)
                    return new, tokens, masked, step_of

                cache, tokens, _, step_of = lax.fori_loop(
                    0, T, one_pass,
                    (cache, tokens, masked,
                     jnp.full(tokens.shape, -1, jnp.int32)))
                with jax.named_scope("block_store"):
                    # the rows of the FINAL tokens (its logits are not
                    # read, so no head runs)
                    _, new = forward_cached(params, tokens, cache, cfg)
                new["pos"] = jnp.where(active & ~done, new["pos"],
                                       cache["pos"])
                hit = (generated & (eos_ids[:, None] >= 0)
                       & (tokens == eos_ids[:, None])).any(axis=1)
                return (new, done | hit), (tokens, step_of)

            (cache, _), (toks, steps) = lax.scan(
                one_block,
                (zero_counters(cache), jnp.zeros(active.shape, bool)),
                jnp.arange(n_blocks))
            return toks, steps, cache, cache_counter_fields(cache)

        self._denoise_blocks = jax.jit(
            _denoise_blocks, static_argnames=("n_blocks",),
            donate_argnums=(1,), compiler_options=_CANONICAL_NUMERICS,
        )
        # per-depth AOT verify programs (warm_aot_verify); missing
        # depths fall back to the jit shape ladder above
        self._aot_verify: dict[int, Any] = {}
        self.aot_verify_info: dict[int, Any] = {}
        # the AOT decode program (warm_aot_step): replaces the jit
        # dispatch of a whole block (n_steps = decode_block, what a
        # live batch runs) when armed, so a fresh serving replica whose
        # (model, slots, max_len) was compiled by ANY earlier replica
        # skips the cold compile (DESIGN.md §17 / ROADMAP item 1
        # leftover). The short calls of a draining batch stay on jit.
        self._aot_step = None
        self.aot_info = None
        self._decoding_gauge = _decoding_slots.labels(self.engine_id)
        self._frozen_gauge = _frozen_row_share.labels(self.engine_id)
        _LIVE_ENGINES.add(self)

    # ------------------------------------------------------- AOT cold start

    def _aot_strategy(self, kind: str, **facts) -> dict:
        """The strategy facts of a serving program's compile-cache
        digest. The digest keys on facts, not on the program's text, so
        what makes two builds' executables NOT interchangeable has to
        be a fact: one compiled WITHOUT canonical numerics (§31
        spec-on/off identity), and one that does not take and donate
        the cache as the model's tree (§23.1, §23.5: this build's,
        loaded by a build that keeps the stack it passed in, would
        delete buffers its caller still reads; that build's, loaded
        here, would hold two stacks). Older entries must miss here,
        and these must miss there."""
        return {"kind": kind, "slots": self.slots,
                "max_len": self.max_len,
                "prefill_len": self.prefill_len, **facts,
                "numerics": "canonical",
                "kv_stack": "tree-carried-donated"}

    def _step_sample_args(self) -> tuple:
        """The exact runtime argument tuple the decode programs share
        (zero requests active), built through the same conversions
        ``step()`` performs — lowering against these pins the true
        avals. ``_verify_block`` takes its guesses after them,
        ``_step_block`` each row's remaining budget
        (`_block_sample_args`)."""
        temp, top_k, top_p, eos_ids = self._sampling_tensors()
        active = np.zeros((self.slots,), bool)
        return (self.params, self._cache, self._last,
                jnp.asarray(self._seeds), jnp.asarray(self._sampled),
                temp, top_k, top_p, jnp.asarray(active), eos_ids)

    def _block_sample_args(self) -> tuple:
        """`_step_sample_args` with ``_step_block``'s last vector."""
        return self._step_sample_args() + (
            jnp.zeros((self.slots,), jnp.int32),)

    def warm_aot_step(self, cache=None):
        """Compile-or-load the decode program a live batch runs (a whole
        block: ``n_steps`` = ``decode_block``) through the
        elastic compile cache; returns the ``AotStep`` evidence (None
        when jax/caching is unavailable). Safe to skip: the jit path
        stays fully functional. What the program DONATES (the cache
        tree and ``last``) is laundered first — a deserialized
        ``Compiled`` skips pjit's input re-staging and updates donated
        buffers in place, so host-built trees must own proper
        per-device buffers before it ever sees them (DESIGN.md §17.4).
        The weights are only read: a copy of them would hold the model
        twice, which a model sized to the chip does not survive."""
        from dlrover_tpu.parallel.compile_cache import (
            abstract_signature,
            compile_fingerprint,
            launder,
            load_or_compile,
        )

        if self._diffusion:
            # no `_step_block` to arm: the decode call is
            # `_denoise_blocks`, on the jit path
            return None
        try:
            self._cache = launder(self._cache)
            self._last = launder(self._last)
            self._samp_cache = None
            sample = self._block_sample_args()
            key, inputs = compile_fingerprint(
                num_nodes=1,
                total_devices=jax.local_device_count(),
                mesh_axes={},
                model=self.cfg,
                strategy=self._aot_strategy(
                    "serving_step", n_steps=self.decode_block),
                args_signature=abstract_signature(sample),
            )
            aot = load_or_compile(
                key, inputs,
                lambda: self._step_block.lower(
                    *sample, n_steps=self.decode_block
                ).compile(compiler_options=_CANONICAL_NUMERICS),
                cache=cache,
            )
        except Exception:  # noqa: BLE001 - cold path must keep serving
            logger.exception("AOT decode-step warmup failed; keeping "
                             "the jit path")
            return None
        self._aot_step = aot.fn
        self.aot_info = aot
        return aot

    def warm_aot_verify(self, depths=None, cache=None):
        """Compile-or-load the speculative verify program for each
        pow2 depth of the engine's ladder (§31). Per-depth cache keys
        are derived through ``verify_key`` so a replica's verify
        ladder lists next to its decode step. No-op when speculation
        is off; safe to skip — the jit ladder stays functional."""
        if not self._spec:
            return []
        from dlrover_tpu.parallel.compile_cache import (
            abstract_signature,
            compile_fingerprint,
            launder,
            load_or_compile,
            verify_key,
        )

        if depths is None:
            depths, d = [], 2
            while d <= self.spec_depth:
                depths.append(d)
                d *= 2
        out = []
        try:
            self._cache = launder(self._cache)
            self._last = launder(self._last)
            self._samp_cache = None
            for depth in depths:
                sample = self._step_sample_args() + (
                    jnp.full((self.slots, depth), -1, jnp.int32),)
                key, inputs = compile_fingerprint(
                    num_nodes=1,
                    total_devices=jax.local_device_count(),
                    mesh_axes={},
                    model=self.cfg,
                    strategy=self._aot_strategy("serving_verify"),
                    args_signature=abstract_signature(sample),
                )
                key = verify_key(key, depth=depth)
                aot = load_or_compile(
                    key, inputs,
                    lambda s=sample: self._verify_block.lower(
                        *s).compile(compiler_options=_CANONICAL_NUMERICS),
                    cache=cache,
                )
                self._aot_verify[depth] = aot.fn
                self.aot_verify_info[depth] = aot
                out.append(aot)
        except Exception:  # noqa: BLE001 - cold path must keep serving
            logger.exception("AOT verify warmup failed; keeping the "
                             "jit ladder")
        return out

    # ----------------------------------------------------------- user API

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        # a weight push (RLHF serving worker swaps actor weights each
        # iteration) makes every cached prefix row stale — KV computed
        # under the OLD weights must never prefix a new generation.
        # Unconditional on purpose: an identity check would silently
        # keep stale rows for callers that mutate the tree in place and
        # re-push the same container. The cost of a redundant clear is
        # one wave of re-prefill; the cost of a stale row is wrong
        # logits with no error. Reuse within a rollout wave survives:
        # the RL engine pushes once per iteration, before the wave.
        # What is kept is the tree as the programs read it (see the
        # class docstring): converted here, once a push.
        self._params = weights_at_rest(value, self.cfg)
        self._prefix_cache.clear()
        self._prefix_lens.clear()

    def _validate(self, prompt: list[int],
                  params: SamplingParams) -> None:
        if not prompt:
            raise ValueError("empty prompt")
        if params.max_new_tokens < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (this engine decodes; "
                "prefill-only scoring is forward_cached directly)"
            )
        if len(prompt) + params.max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens > max_len")
        if self._paging:
            need = -(-(len(prompt) + params.max_new_tokens)
                     // self.page_size)
            if need > self.kv_pages:
                raise ValueError(
                    f"request needs {need} KV pages, pool has "
                    f"{self.kv_pages}"
                )

    def submit(self, prompt: list[int],
               params: SamplingParams | None = None,
               on_token=None, sctx: str = "") -> int:
        params = params or SamplingParams()
        self._validate(list(prompt), params)
        rid = next(self._ids)
        self._queue.append(Request(rid, list(prompt), params, on_token,
                                   sctx=sctx))
        self._submit_time[rid] = time.monotonic()
        return rid

    def submit_prefilled(self, prompt: list[int],
                         params: SamplingParams | None = None,
                         bundle: KVBundle | None = None,
                         on_token=None, sctx: str = "") -> int:
        """Submit a request whose prefill already ran on a PREFILL
        engine: admission installs ``bundle`` (one install, zero
        chunks) instead of re-running the prompt."""
        if bundle is None:
            raise ValueError("submit_prefilled requires a KVBundle")
        self._no_bundles()
        params = params or SamplingParams()
        prompt = list(prompt)
        self._validate(prompt, params)
        if bundle.pos != len(prompt):
            raise ValueError(
                f"bundle covers {bundle.pos} tokens, prompt has "
                f"{len(prompt)}"
            )
        rid = next(self._ids)
        self._queue.append(Request(rid, prompt, params, on_token,
                                   bundle=bundle,
                                   sctx=sctx or bundle.sctx))
        self._submit_time[rid] = time.monotonic()
        return rid

    # ------------------------------------------------------ prefix cache

    def _prefix_lookup(self, prompt: list[int]):
        """Longest chunk-aligned cached prefix of ``prompt``; returns
        ``(start, (row, last))`` or ``None``. jax arrays
        are immutable, so handing out the stored row is alias-safe.

        Probe depth is capped by the set of key lengths actually stored
        (``_prefix_lens``): a miss on a long prompt hashes one tuple per
        DISTINCT stored length, not one per aligned boundary of the
        prompt."""
        P = self.prefill_len
        top = len(prompt) // P * P
        for lo in sorted(self._prefix_lens, reverse=True):
            if lo > top:
                continue
            key = tuple(prompt[:lo])
            ent = self._prefix_cache.get(key)
            if ent is not None:
                # refresh LRU recency (dicts iterate in insertion order)
                self._prefix_cache.pop(key)
                self._prefix_cache[key] = ent
                return lo, ent
        return None

    def _prefix_store(self, key: tuple, ent: tuple) -> None:
        if self._prefix_cache.pop(key, None) is None:
            self._prefix_lens[len(key)] = (
                self._prefix_lens.get(len(key), 0) + 1
            )
        self._prefix_cache[key] = ent
        while len(self._prefix_cache) > self.prefix_cache_entries:
            evicted = next(iter(self._prefix_cache))
            self._prefix_cache.pop(evicted)
            left = self._prefix_lens[len(evicted)] - 1
            if left:
                self._prefix_lens[len(evicted)] = left
            else:
                del self._prefix_lens[len(evicted)]

    # ------------------------------------------------- chunked prefill

    def prefill_begin(self, prompt: list[int]) -> _PrefillRun:
        """Start a chunked prefill into a fresh working row (resuming
        from the longest cached aligned prefix). Drives both admission
        and the disaggregated prefill pool."""
        row = None
        last = None
        start = 0
        if self.prefix_cache_entries:
            self.prefix_cache_queries += 1
            _prefix_cache_queries_total.inc()
            hit = self._prefix_lookup(prompt)
            if hit is not None:
                start, (row, last) = hit
                self.prefix_cache_hits += 1
                _prefix_cache_hits_total.inc()
            _prefix_cache_entries.labels(self.engine_id).set(
                len(self._prefix_cache)
            )
        if row is None:
            row = init_cache(self.cfg, 1, self.max_len)
        upto = len(prompt)
        if self._diffusion:
            # whole blocks only: the remainder opens the first
            # generated block, unmasked
            upto -= upto % self.cfg.block_length
        return _PrefillRun(
            prompt=list(prompt), row=row,
            last=last, next_lo=start, start=start,
            done=start >= upto, upto=upto,
        )

    def prefill_step(self, run: _PrefillRun) -> bool:
        """Run ONE prefill chunk of ``run``; returns True when the
        prompt is fully prefilled. Between the chunk's dispatch and the
        wait for it the host does what it can under the chunk's device
        time (the prefix row stored, the chunk's tokens indexed by the
        request's draft-acceptance shadow); then it blocks on the chunk,
        so admission stall accounting is honest (DESIGN.md §32 has the
        span's host phases)."""
        if run.done:
            return True
        P = self.prefill_len
        t0 = time.monotonic()
        lo = run.next_lo
        chunk = run.prompt[lo: min(lo + P, run.upto)]
        with hot_span("prefill_chunk", remote_parent=run.sctx,
                      request=run.request, tokens=len(chunk),
                      real_tokens=len(chunk), chunk=run.chunks,
                      context=lo) as span:
            opened = time.monotonic()
            toks = np.zeros((1, P), np.int32)
            toks[0, : len(chunk)] = chunk
            tokens = jnp.asarray(toks)
            true_len = jnp.asarray(len(chunk), jnp.int32)
            built = time.monotonic()
            run.row, run.last, counted = self._prefill_chunk(
                self.params, tokens, run.row, true_len)
            dispatched = time.monotonic()
            final_top = run.upto // P * P
            if self.prefix_cache_entries and len(chunk) == P:
                # snapshot the FINAL aligned boundary always;
                # intermediate boundaries only when extending an
                # already-cached prefix (start > 0, the
                # shared-system-prompt chain). A cold non-sharing prompt
                # then adds ONE entry instead of top/P, so a wave of
                # long unrelated prompts can no longer churn the LRU and
                # evict the shared prefixes that actually hit.
                if lo + P == final_top or run.start > 0:
                    self._prefix_store(
                        tuple(run.prompt[: lo + P]),
                        (run.row, run.last),
                    )
            run.next_lo = lo + P
            run.chunks += 1
            self._chunks_run += 1
            run.done = run.next_lo >= run.upto
            indexed = 0
            if self._obs is not None and run.request >= 0:
                # an admission's chunk: the shadow indexes its tokens
                # while the device runs them, where this thread would
                # only wait (the prefill pool's runs have no request)
                indexed = self._obs.note_prefilled(
                    run.request, run.prompt, lo, lo + len(chunk))
            hidden = time.monotonic()
            jax.block_until_ready(run.last)
            waited = time.monotonic()
            # the chunk is done: its counters cost no wait of their own.
            # The host's seconds while the device holds nothing of this
            # chunk (`build_s`, `dispatch_s`, `after_s`) and its wait;
            # what ran under the chunk's device time is in none of them
            span.set(indexed_tokens=indexed,
                     **_counted(jax.device_get(counted)),
                     build_s=round(built - opened, 6),
                     dispatch_s=round(dispatched - built, 6),
                     wait_s=round(waited - hidden, 6),
                     after_s=round(time.monotonic() - waited, 6))
        run.work_s += time.monotonic() - t0
        return run.done

    def make_bundle(self, run: _PrefillRun) -> KVBundle:
        """Package a finished prefill run as a page-granular host
        bundle for handoff to a decode engine."""
        self._no_bundles()
        if not run.done:
            raise ValueError("prefill run not finished")
        P = self.page_size
        n_tok = len(run.prompt)
        n_pages = -(-n_tok // P)
        # device_get can return views of device buffers on CPU — copy,
        # so the bundle owns its bytes wherever it travels
        stacks = {}
        for name, stack in cache_stacks(run.row).items():
            rows = np.ascontiguousarray(
                np.asarray(jax.device_get(stack))[:, 0, : n_pages * P])
            stacks[name] = rows.reshape(
                (rows.shape[0], n_pages, P) + rows.shape[2:])
        top = n_tok // self.prefill_len * self.prefill_len
        return KVBundle(
            stacks=stacks, pos=n_tok,
            last=np.asarray(jax.device_get(run.last)),
            page_size=P, prefix_key=tuple(run.prompt[:top]),
        )

    def _no_bundles(self) -> None:
        if self._stateful:
            raise NotImplementedError(
                "handed-over KVBundles (make_bundle, submit_prefilled) "
                "with a model that carries state (or a ring: a windowed "
                "layer's rows): a bundle is pages of token-addressed rows, "
                "and the state or ring at the prompt's end has no page")
        if self._diffusion:
            raise NotImplementedError(
                "handed-over KVBundles (make_bundle, submit_prefilled) "
                "with a block-diffusion model: a bundle carries (pos, "
                "last) for a next token, and this model's first block "
                "opens with the prompt's remainder, which no bundle "
                "holds")

    def _run_from_bundle(self, req: Request) -> _PrefillRun:
        """Rebuild a finished working row from a handoff bundle (the
        decode-side half of the KV handoff — pad the shipped pages to
        a max_len row, then install through the normal path)."""
        b = req.bundle
        if b.page_size != self.page_size:
            raise ValueError(
                f"bundle page_size {b.page_size} != engine page_size "
                f"{self.page_size}"
            )
        def pad(pages):
            # one fresh buffer per tensor: CPU device_put may ADOPT an
            # aligned writable host buffer (DESIGN.md §17.4), so two
            # stacks must never share one staging array
            L, covered = pages.shape[0], pages.shape[1] * b.page_size
            row = np.zeros((L, 1, self.max_len) + pages.shape[3:],
                           dtype=pages.dtype)
            row[:, 0, :covered] = pages.reshape(
                (L, covered) + pages.shape[3:])
            return jnp.asarray(row)

        row = {name: pad(pages) for name, pages in b.stacks.items()}
        row.update(self._row_extras, pos=jnp.asarray(b.pos, jnp.int32))
        return _PrefillRun(
            prompt=list(req.prompt), row=row,
            last=jnp.asarray(b.last), next_lo=len(req.prompt),
            start=0, done=True,
        )

    # --------------------------------------------------------- admission

    def _pages_needed(self, req: Request) -> int:
        total = len(req.prompt) + req.params.max_new_tokens
        return -(-total // self.page_size)

    # ------------------------------------------------- COW page ledger

    def _lease_page(self) -> int:
        pid = self._free_pages.pop()
        self._page_refs[pid] = 1
        return pid

    def _release_ref(self, pid: int) -> None:
        """Decref one page-table reference; at zero the page is
        unregistered from the sharing index and returned to the free
        list. Raises on a negative refcount — that is corruption, not
        a recoverable state."""
        left = self._page_refs.get(pid, 0) - 1
        if left < 0:
            raise AssertionError(
                f"negative refcount for KV page {pid}"
            )
        if left:
            self._page_refs[pid] = left
            return
        del self._page_refs[pid]
        digest = self._page_digest.pop(pid, None)
        if digest is not None and self._share_index.get(digest) == pid:
            del self._share_index[digest]
        self._free_pages.append(pid)

    def _share_match(self, req: Request) -> list[int]:
        """Resident physical pages matching this prompt's full-prefix
        chain digests, contiguous from page 0 (a chain digest only
        certifies a page when the whole prefix through it matches)."""
        if not self._cow or self._digest_store is None:
            return []
        out: list[int] = []
        for digest in self._digest_store.pages(req.id):
            pid = self._share_index.get(digest)
            if pid is None:
                break
            out.append(pid)
        return out

    def _cow_break(self, slot: int, idx: int) -> None:
        """Copy-on-write: a scatter is about to write content into a
        shared physical page (the slot's dense row diverged inside the
        entry's span), so re-point the table entry at a fresh private
        page and drop the shared reference. Unreachable under the
        share policy (only full prompt-prefix pages are shared, decode
        never writes below the prompt) — kept live as the corruption
        guard the sharing discipline rests on."""
        if not self._free_pages:
            raise RuntimeError(
                "KV pool exhausted during copy-on-write break"
            )
        req = self._active[slot]
        old = self._slot_pages[slot][idx]
        fresh = self._lease_page()
        self._slot_pages[slot][idx] = fresh
        shared = self._slot_shared[slot]
        if shared is not None:
            shared.discard(idx)
        self._release_ref(old)
        self.cow_breaks_total += 1
        _kv_cow_breaks_total.inc()
        get_journal().emit(
            "kv_cow", request=req.id, kind="break", page=old,
            fresh=fresh, remote_parent=req.sctx,
        )

    def kv_page_ledger(self) -> dict:
        """Conservation snapshot of the page pool: every physical page
        is exactly one of free or leased-with-positive-refcount, free
        pages are distinct, and the sharing index round-trips through
        its reverse map. Tests assert ``ok`` after every engine test."""
        leased = dict(self._page_refs)
        free = list(self._free_pages)
        ok = (not self._paging) or (
            len(free) + len(leased) == self.kv_pages
            and len(set(free)) == len(free)
            and not (set(free) & set(leased))
            and min(leased.values(), default=1) >= 1
            and all(self._share_index.get(d) == p
                    for p, d in self._page_digest.items())
        )
        return {
            "total": self.kv_pages,
            "free": len(free),
            "leased": len(leased),
            "min_ref": min(leased.values(), default=1),
            "shared_entries": self.cow_pages_saved,
            "ok": ok,
        }

    def _take_slot(self) -> int | None:
        """A free slot, or (paging only) free one by parking the
        longest-running active generation that has decoded at least one
        page since its install (the anti-thrash quantum)."""
        for s in range(self.slots):
            if self._active[s] is None:
                return s
        if not self._paging:
            return None
        victim = None
        for s in range(self.slots):
            if self._since_install[s] < self.page_size:
                continue
            if victim is None or (len(self._emitted[s])
                                  > len(self._emitted[victim])):
                victim = s
        if victim is None:
            return None
        self._park_slot(victim)
        return victim

    def _park_slot(self, slot: int) -> None:
        req = self._active[slot]
        pages = self._slot_pages[slot] or []
        shared = self._slot_shared[slot] or set()
        pos_now = int(self._cache["pos"][slot])
        plen = len(req.prompt)
        table = np.zeros((self.pages_per_slot,), np.int32)
        for i in range(len(pages)):
            # immutable entries (attached shares + this slot's own
            # registered prefix pages) are already resident and must
            # never be scattered to — their table slot points at the
            # scratch page. A write that WOULD land in one (the
            # decode-dirty span [plen, pos) overlapping its pages)
            # breaks the share copy-on-write first.
            immutable = (i in shared
                         or pages[i] in self._page_digest)
            if (immutable and i * self.page_size < pos_now
                    and (i + 1) * self.page_size > plen):
                self._cow_break(slot, i)
                immutable = False
            table[i] = 0 if immutable else pages[i]
        self._kpool, self._vpool = self._park_out(
            self._cache["k"], self._cache["v"], self._kpool,
            self._vpool, jnp.asarray(slot, jnp.int32),
            jnp.asarray(table),
        )
        self._parked.append(_Parked(
            req=req, pages=pages,
            pos=pos_now,
            last=self._last[slot],
            seed=int(self._seeds[slot]),
            sampled=int(self._sampled[slot]),
            emitted=self._emitted[slot],
            shared=set(self._slot_shared[slot] or ()),
        ))
        self._active[slot] = None
        self._emitted[slot] = []
        self._slot_pages[slot] = None
        self._slot_shared[slot] = None
        self._samp_cache = None
        self.kv_parked_total += 1
        _kv_parked_total.inc()
        if self._obs is not None:
            self._obs.note_park(req.id)

    def _resume_parked(self, slot: int, parked: _Parked) -> None:
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[: len(parked.pages)] = parked.pages
        (self._cache["k"], self._cache["v"], self._cache["pos"],
         self._last) = self._resume_install(
            self._cache["k"], self._cache["v"], self._cache["pos"],
            self._last, self._kpool, self._vpool, jnp.asarray(table),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(parked.pos, jnp.int32), parked.last,
        )
        self._active[slot] = parked.req
        self._emitted[slot] = parked.emitted
        self._slot_pages[slot] = parked.pages
        self._slot_shared[slot] = set(parked.shared)
        self._seeds[slot] = np.uint32(parked.seed)
        self._sampled[slot] = parked.sampled
        self._since_install[slot] = 0
        self._samp_cache = None
        jax.block_until_ready(self._last)
        if self._obs is not None:
            self._obs.note_resume(parked.req.id)
        get_journal().emit(
            "engine_admit", request=parked.req.id, kind="resume",
            chunks=0, emitted=len(parked.emitted),
            remote_parent=parked.req.sctx,
        )

    def _start_admission(self) -> bool:
        """Pop the queue head into a pending admission (reserving its
        pages) if capacity allows. FIFO on purpose: head-of-line
        bypass would starve long prompts under page pressure. The
        admission carries what the request's `kv_install` span will say
        of its time so far: how long it queued, and the host seconds
        THIS call spent taking it up (a page-blocked head's earlier
        tries lie in its queue wait)."""
        if not self._queue:
            return False
        req = self._queue[0]
        taken = time.monotonic()
        if self._digest_store is not None:
            self._digest_store.start(req.id, req.prompt)
        pages: list[int] = []
        shared_n = 0
        if self._paging:
            need = self._pages_needed(req)  # fits: validated at submit
            shared = self._share_match(req)
            if len(self._free_pages) < need - len(shared):
                if self._obs is not None:
                    self._obs.note_page_blocked()
                return False
            # admission capacity counts UNIQUE pages: attached shares
            # are incref'd (a pending admission holds its references —
            # the owner retiring cannot free them out from under it),
            # only the remainder is leased from the free list
            for pid in shared:
                self._page_refs[pid] += 1
            fresh = [self._lease_page()
                     for _ in range(need - len(shared))]
            pages = shared + fresh
            shared_n = len(shared)
            if shared_n:
                self.cow_pages_shared_total += shared_n
                _kv_cow_shared_total.inc(shared_n)
                get_journal().emit(
                    "kv_cow", request=req.id, kind="share",
                    shared=shared_n, fresh=len(fresh),
                    remote_parent=req.sctx,
                )
            if self._obs is not None:
                self._obs.note_pages_leased(req.id, len(fresh))
        self._queue.popleft()
        if req.bundle is not None:
            run = self._run_from_bundle(req)
            kind = "handoff"
        else:
            run = self.prefill_begin(req.prompt)
            kind = "hit" if run.start else "cold"
        run.request, run.sctx = req.id, req.sctx
        queue_wait_s = round(taken - self._submit_time.pop(req.id), 6)
        _queue_wait_seconds.labels(self.engine_id).observe(queue_wait_s)
        self._pending = _PendingAdmit(
            req=req, run=run, pages=pages, kind=kind,
            shared=set(range(shared_n)), taken=taken,
            queue_wait_s=queue_wait_s,
            start_s=round(time.monotonic() - taken, 6))
        return True

    def _install_admit(self, slot: int, pa: _PendingAdmit) -> int:
        """Install a finished prefill run into ``slot``; returns the
        prompt tokens the shadow had left to index here (what no chunk
        carried: under ``prefill_len`` for a cold prompt)."""
        req, run = pa.req, pa.run
        last = run.last
        if last is None:
            # a prompt shorter than one block prefills nothing
            last = jnp.zeros((self.cfg.vocab_size,), jnp.float32)
        self._cache, self._last = self._install(
            self._cache, self._last, run.row, last,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(run.upto, jnp.int32),
        )
        # under the install's device time, as a chunk's tokens under the
        # chunk's
        indexed = (self._obs.note_admitted(req)
                   if self._obs is not None else 0)
        jax.block_until_ready(self._last)
        self._active[slot] = req
        self._emitted[slot] = []
        self._unmask[slot] = []
        self._opening[slot] = (list(req.prompt[run.upto:])
                               if self._diffusion else None)
        self._slot_pages[slot] = pa.pages
        self._slot_shared[slot] = set(pa.shared)
        self._since_install[slot] = 0
        if self._cow and pa.pages:
            self._materialize_prefix(slot, pa)
        seed = (req.params.seed if req.params.seed is not None
                else int(self._seed_gen.integers(0, 2**32)))
        # normalize arbitrary ints (time_ns(), 64-bit random) into
        # the uint32 fold_in domain instead of overflowing mid-run
        self._seeds[slot] = np.uint32(seed % (2**32))
        self._sampled[slot] = 0
        self._samp_cache = None
        journal = get_journal()
        journal.emit(
            "engine_admit", request=req.id, kind=pa.kind,
            chunks=run.chunks, dur=round(run.work_s, 6),
            tokens=len(req.prompt), remote_parent=req.sctx,
        )
        if pa.kind == "handoff":
            _kv_handoffs_total.inc()
            journal.emit(
                "kv_handoff", request=req.id,
                pages=int(next(iter(
                    req.bundle.stacks.values())).shape[1]),
                tokens=len(req.prompt),
                bytes=int(sum(a.nbytes
                              for a in req.bundle.stacks.values())),
                remote_parent=req.sctx,
            )
        return indexed

    def _materialize_prefix(self, slot: int, pa: _PendingAdmit) -> None:
        """Scatter the freshly installed row's FULL prompt-prefix
        pages into the pool and register their chain digests, so later
        admissions dedup against them (§31). Attached shares are
        already resident; only fresh, not-yet-registered prefix pages
        are written. One extra `_park_out` dispatch per admission that
        registers anything — the price of a resident sharing index."""
        digests = self._digest_store.pages(pa.req.id)
        n_pref = min(len(digests), len(pa.pages))
        fresh = [i for i in range(n_pref)
                 if i not in pa.shared
                 and digests[i] not in self._share_index]
        if not fresh:
            return
        table = np.zeros((self.pages_per_slot,), np.int32)
        for i in fresh:
            table[i] = pa.pages[i]
        self._kpool, self._vpool = self._park_out(
            self._cache["k"], self._cache["v"], self._kpool,
            self._vpool, jnp.asarray(slot, jnp.int32),
            jnp.asarray(table),
        )
        for i in fresh:
            self._share_index[digests[i]] = pa.pages[i]
            self._page_digest[pa.pages[i]] = digests[i]

    def _admit_tick(self) -> bool:
        """At most ONE unit of admission work — a single prefill chunk,
        plus at most one install (or one resume). ``_step`` runs one
        unit per decode step of the block beside a live batch, so
        active decodes wait for one chunk's compute per token. Returns
        True when device work ran (the caller observes the stall
        histogram)."""
        if self._pending is None:
            # resumes first: their pages are already paid for and their
            # requester has waited longest
            if self._parked:
                slot = self._take_slot()
                if slot is None:
                    return False
                self._resume_parked(slot, self._parked.popleft())
                return True
            if not self._start_admission():
                return False
        pa = self._pending
        worked = False
        if not pa.run.done:
            self.prefill_step(pa.run)
            worked = True
        if pa.run.done:
            slot = self._take_slot()
            if slot is not None:
                with hot_span(
                        "kv_install", remote_parent=pa.req.sctx,
                        request=pa.req.id, slot=slot,
                        tokens=len(pa.req.prompt),
                        state_bytes=self.state_bytes_per_slot,
                        queue_wait_s=pa.queue_wait_s, start_s=pa.start_s,
                        admit_wall_s=round(time.monotonic() - pa.taken, 6),
                        chunk_work_s=round(pa.run.work_s, 6),
                        chunks=pa.run.chunks) as span:
                    span.set(
                        indexed_tokens=self._install_admit(slot, pa))
                self._pending = None
                worked = True
        return worked

    def _admit(self) -> None:
        """Drain every possible admission synchronously (compat/test
        helper; ``step()`` uses the incremental ``_admit_tick``)."""
        while self._admit_tick():
            pass

    # ------------------------------------------------------------- decode

    def _sampling_tensors(self):
        if self._samp_cache is not None:
            return self._samp_cache
        temp = np.ones((self.slots,), np.float32)
        top_p = np.ones((self.slots,), np.float32)
        top_k = np.zeros((self.slots,), np.int32)
        eos = np.full((self.slots,), -1, np.int32)
        for s, req in enumerate(self._active):
            if req is None:
                continue
            temp[s] = req.params.temperature
            top_p[s] = req.params.top_p
            top_k[s] = req.params.top_k or 0
            if req.params.eos_id is not None:
                eos[s] = req.params.eos_id
        self._samp_cache = (jnp.asarray(temp), jnp.asarray(top_k),
                            jnp.asarray(top_p), jnp.asarray(eos))
        return self._samp_cache

    def _sampling_rows(self) -> int:
        """Live rows that sample (``temperature`` > 0 as the programs
        compare it, in float32): the decode programs run the sampler
        when this is over 0 and take the argmax when it is 0."""
        return sum(req is not None
                   and bool(np.float32(req.params.temperature) > 0)
                   for req in self._active)

    def _remaining(self) -> np.ndarray:
        """Tokens each slot's budget still allows, [slots] int32 (an
        empty slot: 0)."""
        return np.array(
            [0 if req is None
             else req.params.max_new_tokens - len(self._emitted[s])
             for s, req in enumerate(self._active)], np.int32)

    def _block_size(self) -> int:
        """Steps of the next decode call, sized by the active row with
        the MOST left: a whole ``decode_block`` while any row has that
        much, else the smallest power of two that holds the longest
        tail (a lone request's last tokens and a draining batch ride
        one short call; the powers keep distinct compiles bounded). A
        row with less left stops inside the call, at its budget as at
        its eos: both are observed per slot inside the compiled scan
        and retired on the host."""
        remaining = self._remaining()
        if self._diffusion:
            # in TOKENS a row, as ever: whole blocks, `decode_block //
            # block_length` of them (at least one), never a block past
            # a row's budget (its LAST block may be cut by it: -(-r //
            # B) blocks hold r tokens), on the power-of-two ladder
            B = self.cfg.block_length
            least = int(remaining[remaining > 0].min())
            cap = min(max(1, self.decode_block // B), -(-least // B))
            block = 1
            while block * 2 <= cap:
                block *= 2
            return block * B
        most = int(remaining.max())
        block = 1
        while block < most:
            block *= 2
        return min(self.decode_block, block)

    def _steps_ahead(self) -> int:
        """TOKENS each row decoding NOW is about to receive: the verify
        block's depth when speculation plans one, else the block's
        steps (an autoregressive step yields a token a row; a
        block-diffusion call's blocks yield ``block_length`` each, in
        ``denoising_steps + 1`` passes). Read before admission — it is
        the admission's budget of units (DESIGN.md §23.1: a row
        decoding waits for at most one unit per token it receives; a
        row inside its LAST block receives fewer than the block's
        steps, the one exception)."""
        plan = self._spec_plan() if self._spec else None
        return plan[0] if plan is not None else self._block_size()

    def _spec_plan(self):
        """This step's verify depth + per-slot draft feed, or None for
        the plain block path. Depth policy (§31): k tracks the
        observatory's accept-run p50 prior (cold start: 2), clamped to
        ``spec_depth`` and to every ACTIVE slot's remaining budget (so
        no row can overrun its page lease or max_len), then snapped to
        the pow2 ladder. Greedy rows with drafter evidence and a live
        (non-collapsed) acceptance record speculate; everything else
        advances exactly one token inside the same dispatch — which is
        why, when the engine's block ladder would scan more than one
        step, a verify only dispatches if EVERY active slot drafted: a
        non-drafting slot inside a verify advances 1 token where the
        block scan would have given it ``block``, so mixed dispatches
        are a strict loss the moment block > 1."""
        drafts: dict[int, list[int]] = {}
        rem_min = None
        n_active = 0
        for s, req in enumerate(self._active):
            if req is None:
                continue
            n_active += 1
            rem = req.params.max_new_tokens - len(self._emitted[s])
            rem_min = rem if rem_min is None else min(rem_min, rem)
            if req.params.temperature > 0:
                continue               # greedy-only by design
            st = self._spec_acc.get(req.id)
            if st is not None and st[2]:
                continue               # collapsed to k=1
            shadow = self._obs._shadow.get(req.id)
            if shadow is None:
                continue
            d = shadow.draft(self.spec_depth)
            if d:
                drafts[s] = d
        if not drafts:
            return None
        if self._block_size() > 1 and len(drafts) < n_active:
            return None
        prior = self._obs._run_percentile(0.50)
        # floor 4, not 2: the verify program's per-token cost only
        # beats the block scan once a couple of drafts can land, so a
        # cold prior must not pin the ladder at its least profitable
        # depth — per-request collapse already protects the hopeless
        kmax = min(self.spec_depth, max(4, prior + 1))
        cap = min(kmax, rem_min)
        depth = 1
        while depth * 2 <= cap:
            depth *= 2
        if depth < 2:
            return None
        guesses = np.full((self.slots, depth), -1, np.int32)
        for s, d in drafts.items():
            for i in range(min(depth, len(d))):
                guesses[s, i] = d[i]
        return depth, guesses

    def _spec_score(self, guesses, toks_sn, depth: int) -> None:
        """Per-request live acceptance from one verify step: each REAL
        fed guess is scored against the chain-true token at its
        position, sequentially up to (and including) the first miss —
        the standard speculative accounting. Collapse drops the
        request to k=1 for good."""
        for s, req in enumerate(self._active):
            if req is None or guesses[s, 0] < 0:
                continue
            ac = sc = 0
            for i in range(1, depth):
                g = int(guesses[s, i])
                if g < 0:
                    break
                sc += 1
                if g == int(toks_sn[s, i]):
                    ac += 1
                else:
                    break
            if not sc:
                continue
            st = self._spec_acc.setdefault(req.id, [0, 0, 0])
            st[0] += ac
            st[1] += sc
            self.spec_drafts_accepted += ac
            self.spec_drafts_scored += sc
            if (not st[2] and st[1] >= _SPEC_COLLAPSE_MIN_SCORED
                    and st[0] / st[1] < _SPEC_COLLAPSE_RATE):
                st[2] = 1
                self.spec_collapsed_total += 1
                _spec_collapsed_total.inc()

    def step(self) -> int:
        """Admit waiting work (beside a live batch: at most one chunk
        per decode step of the block), decode one token (or one
        compiled block) for every active slot, retire finished ones.
        Returns number of active slots."""
        with hot_span("engine_step", queued=len(self._queue)) as span:
            fields, active = self._step()
            span.set(**fields)
        # slots in this step's decode call: what `slot_occupancy`
        # (requests a replica holds) cannot see
        self._decoding_gauge.set(fields["decoding_slots"])
        return active

    def _step(self) -> tuple[dict, int]:
        """(the `engine_step` span's late fields: slots in the decode
        call, its steps, prefill chunks run before it, the host's
        seconds for the decode call; slots active after)."""
        had_active = any(r is not None for r in self._active)
        chunks_before = self._chunks_run
        admitted = time.monotonic()
        if not had_active:
            # nobody was decoding: no stall to bound, so fill the
            # batch like the pre-chunking admission did (cold bursts —
            # the dominant test/rollout shape — keep their old step
            # count; the per-token bound only governs LIVE batches)
            while (self._admit_tick()
                   and any(r is None for r in self._active)
                   and (self._queue or self._parked
                        or self._pending is not None)):
                pass
            admitted = time.monotonic()
        elif self._queue or self._parked or self._pending is not None:
            # a live batch: one unit of admission work (a chunk and an
            # install at most, or a resume) per decode step of the block
            # the rows already decoding are about to run, so each of
            # them waits for at most one chunk per token it receives
            # (one unit an engine step at decode_block 1). An install
            # clears `_pending`, so the next unit starts the next prompt.
            budget = self._steps_ahead()
            t0 = time.monotonic()
            units = 0
            while units < budget and self._admit_tick():
                units += 1
            admitted = time.monotonic()
            if units:
                # the decode stall this admission cost the live batch:
                # one observation an engine step, of <= `budget` chunks
                _decode_stall_seconds.observe(admitted - t0)
        fields = {"decoding_slots": 0, "n_steps": 0,
                  "prefill_chunks": self._chunks_run - chunks_before,
                  "decode_host_s": 0.0}
        active_mask = np.array(
            [r is not None for r in self._active], bool
        )
        if not active_mask.any():
            return fields, 0
        decoding = int(active_mask.sum())
        sampling_rows = self._sampling_rows()
        temp, top_k, top_p, eos_ids = self._sampling_tensors()
        args = (
            self.params, self._cache, self._last,
            jnp.asarray(self._seeds), jnp.asarray(self._sampled),
            temp, top_k, top_p, jnp.asarray(active_mask), eos_ids,
        )
        steps = None
        plan = self._spec_plan() if self._spec else None
        if self._diffusion:
            n_steps, toks, counts, steps, wait_s = self._denoise_call(
                args, active_mask, decoding, sampling_rows)
        elif plan is not None:
            depth, guesses = plan
            n_steps = depth
            fn = self._aot_verify.get(depth, self._verify_block)
            with hot_span("decode_block", slots=decoding,
                          n_steps=depth,
                          sampling_rows=sampling_rows) as span:
                toks_dev, cache, last, acc_dev, counted = fn(
                    *args, jnp.asarray(guesses))
                (toks_sn, acc, counted), wait_s = _fetch(
                    span, (toks_dev, acc_dev, counted))
                toks_sn, acc = np.asarray(toks_sn), np.asarray(acc)
                span.set(**self._note_counted(counted))
            toks = toks_sn.T                     # [depth, slots]
            counts = acc.astype(np.int64)        # inactive rows: 0
            self._sampled += counts
            self.spec_steps_total += 1
            _spec_verify_steps_total.inc()
            extra = int(counts.sum()) - decoding
            if extra > 0:
                self.spec_extra_tokens_total += extra
                _spec_extra_tokens_total.inc(extra)
            self._spec_score(guesses, toks_sn, depth)
            self._cache, self._last = cache, last
        else:
            n_steps = block = self._block_size()
            remaining = self._remaining()
            # a row's tokens of this call: up to its budget (an empty
            # slot: 0); past it the row is frozen in the program
            counts = np.minimum(remaining, block)
            frozen = decoding * block - int(counts.sum())
            args += (jnp.asarray(remaining),)
            with hot_span("decode_block", slots=decoding, n_steps=block,
                          frozen_row_steps=frozen,
                          sampling_rows=sampling_rows) as span:
                if block == self.decode_block and self._aot_step is not None:
                    toks_dev, cache, last, counted = self._aot_step(*args)
                else:
                    toks_dev, cache, last, counted = self._step_block(
                        *args, n_steps=block,
                    )
                # the model's counters ride the tokens' device_get
                (toks, counted), wait_s = _fetch(span,
                                                 (toks_dev, counted))
                toks = np.asarray(toks)
                span.set(**self._note_counted(counted))
            self._frozen_gauge.set(frozen / (decoding * block))
            self._sampled += counts
            self._cache, self._last = cache, last
        # the host's seconds for this step's decode call: all that ran
        # between the admission's end and the tokens' hand-out, but the
        # wait for the device
        fields.update(
            decoding_slots=decoding, n_steps=n_steps,
            decode_host_s=round(time.monotonic() - admitted - wait_s, 6))
        with hot_span("engine_emit", tokens=int(counts.sum())):
            self._emit(toks, counts, steps)
        return fields, sum(r is not None for r in self._active)

    def _denoise_call(self, args, active_mask, decoding: int,
                      sampling_rows: int):
        """One block-diffusion decode call: ``(forward passes run, tokens
        [most a row, slots], how many each row generated, the pass that
        unmasked each, the seconds the host waited for the device)``.
        ``_emit`` cuts a row at its budget and at its first eos, as
        ever."""
        c = self.cfg
        B, T = c.block_length, c.denoising_steps
        n_blocks = self._block_size() // B
        tokens0 = np.full((self.slots, B), c.mask_token_id, np.int32)
        masked0 = np.ones((self.slots, B), bool)
        for s, opening in enumerate(self._opening):
            if opening:
                tokens0[s, : len(opening)] = opening
                masked0[s, : len(opening)] = False
            self._opening[s] = None
        params, cache, _last, *rest = args
        n_steps = n_blocks * (T + 1)
        with hot_span("decode_block", slots=decoding, n_steps=n_steps,
                      blocks=n_blocks, denoise_passes=n_blocks * T,
                      store_passes=n_blocks,
                      sampling_rows=sampling_rows) as span:
            toks_dev, steps_dev, cache, counted = self._denoise_blocks(
                params, cache, jnp.asarray(tokens0), jnp.asarray(masked0),
                *rest, n_blocks=n_blocks)
            (blocks, unmasked, counted), wait_s = _fetch(
                span, (toks_dev, steps_dev, counted))
            # [n_blocks, slots, B] -> a row's generated positions in
            # order: all of a block but the prompt's remainder
            generated = np.ones(blocks.shape, bool)
            generated[0] = masked0
            counts = np.where(active_mask, generated.sum(axis=(0, 2)), 0)
            toks = np.zeros((int(counts.max()), self.slots), np.int32)
            steps = np.zeros_like(toks)
            delivered = 0
            for s in np.flatnonzero(active_mask):
                keep = generated[:, s]
                toks[: counts[s], s] = blocks[:, s][keep]
                steps[: counts[s], s] = unmasked[:, s][keep]
                req = self._active[s]
                delivered += min(int(counts[s]), req.params.max_new_tokens
                                 - len(self._emitted[s]))
            span.set(tokens_out=delivered, **self._note_counted(counted))
        _tokens_per_pass.labels(self.engine_id).set(
            delivered / (decoding * n_steps))
        self._sampled[active_mask] += n_blocks * T
        self._cache = cache
        return n_steps, toks, counts, steps, wait_s

    def _note_counted(self, counted: dict) -> dict:
        """A decode call's counters as span fields, and onto their
        gauges (none for a model that counts nothing)."""
        fields = _counted(counted)
        for name in fields.keys() & _COUNTER_GAUGES.keys():
            _COUNTER_GAUGES[name].labels(self.engine_id).set(fields[name])
        return fields

    def _emit(self, toks, counts, steps=None) -> None:
        """The host's share of a step: every new token to its request
        (digest store, observatory, the streaming callback), finished
        requests retired."""
        for s, req in enumerate(self._active):
            if req is None:
                continue
            p = req.params
            for j in range(int(counts[s])):
                t = int(toks[j, s])
                self._emitted[s].append(t)
                if steps is not None:
                    self._unmask[s].append(int(steps[j, s]))
                self._since_install[s] += 1
                if self._digest_store is not None:
                    self._digest_store.extend(req.id, t)
                if self._obs is not None:
                    self._obs.observe_token(req.id, t)
                if req.on_token is not None:
                    try:
                        req.on_token(req.id, t)
                    except Exception:  # noqa: BLE001 - a streaming
                        logger.exception(  # consumer must not kill decode
                            "on_token callback failed (request %d)",
                            req.id,
                        )
                if p.eos_id is not None and t == p.eos_id:
                    self._retire(s, "eos")
                    break
                if len(self._emitted[s]) >= p.max_new_tokens:
                    self._retire(s, "length")
                    break
        if self._obs is not None:
            self._obs.on_step()

    def _retire(self, slot: int, reason: str) -> None:
        req = self._active[slot]
        self._results.append(Result(
            id=req.id, prompt=req.prompt,
            tokens=list(self._emitted[slot]), finish_reason=reason,
            unmask_steps=list(self._unmask[slot]),
        ))
        if self._obs is not None:
            self._obs.note_retire(req.id)
        if self._digest_store is not None:
            self._digest_store.drop(req.id)
        st = self._spec_acc.pop(req.id, None)
        if st is not None and st[1]:
            get_journal().emit(
                "spec_verify", request=req.id, accepted=st[0],
                scored=st[1], collapsed=bool(st[2]),
                remote_parent=req.sctx,
            )
        self._active[slot] = None
        self._emitted[slot] = []
        self._unmask[slot] = []
        self._opening[slot] = None
        self._samp_cache = None
        pages = self._slot_pages[slot]
        if pages:
            for pid in pages:
                self._release_ref(pid)
        self._slot_pages[slot] = None
        self._slot_shared[slot] = None

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def cow_pages_saved(self) -> int:
        """Page-table entries currently deduped onto shared physical
        pages across active, parked and pending requests — each is one
        physical page the pool did not have to lease."""
        saved = sum(len(s) for s in self._slot_shared if s)
        saved += sum(len(p.shared) for p in self._parked)
        if self._pending is not None:
            saved += len(self._pending.shared)
        return saved

    @property
    def spec_accept_rate(self) -> float:
        """Live draft acceptance: accepted / scored REAL draft tokens
        across verify steps (0.0 before any draft was scored)."""
        if not self.spec_drafts_scored:
            return 0.0
        return self.spec_drafts_accepted / self.spec_drafts_scored

    @property
    def observatory(self) -> ServingObservatory | None:
        return self._obs

    def observatory_snapshot(self) -> dict | None:
        """Last ``kv_pool`` sample (None when the observatory is off or
        has not sampled yet) — the gateway health tick's per-replica
        read, safe from any thread."""
        if self._obs is None:
            return None
        return self._obs.snapshot() or None

    @property
    def outstanding(self) -> int:
        """Queued + admitting + parked + active requests (the gateway
        router's load signal)."""
        return (len(self._queue)
                + (1 if self._pending is not None else 0)
                + len(self._parked)
                + sum(r is not None for r in self._active))

    def poll_results(self) -> list[Result]:
        """Return (and clear) results retired since the last poll.

        The incremental twin of ``run()`` for callers that drive
        ``step()`` themselves — the gateway replica loop retires
        finished requests between decode iterations while others keep
        decoding."""
        out, self._results = self._results, []
        return out

    def run(self, max_iters: int = 100000) -> list[Result]:
        """Drain the queue and all active slots; returns results in
        completion order."""
        for _ in range(max_iters):
            if not self.outstanding:
                break
            self.step()
        else:
            raise RuntimeError(
                f"run() exhausted {max_iters} iterations with "
                f"{len(self._queue)} queued, {len(self._parked)} "
                f"parked and "
                f"{sum(r is not None for r in self._active)} active "
                "requests still unfinished"
            )
        out, self._results = self._results, []
        return out


def _fetch(span: HotSpan, outputs) -> tuple:
    """A decode call's results on the host, and the seconds the host
    waited for them, which are the `decode_block` span's `wait_s`."""
    asked = time.monotonic()
    got = jax.device_get(outputs)
    wait_s = round(time.monotonic() - asked, 6)
    span.set(wait_s=wait_s)
    return got, wait_s


def _counted(counted: dict) -> dict:
    """Fetched counter scalars as plain numbers for a span's fields."""
    return {name: (float(v) if np.issubdtype(np.asarray(v).dtype,
                                             np.floating) else int(v))
            for name, v in counted.items()}


def check_kv_ledgers() -> list[str]:
    """Page-ledger conservation across every live engine in this
    process (the autouse test fixture's hook): returns one description
    per violated ledger, empty when all conserve."""
    bad = []
    for eng in list(_LIVE_ENGINES):
        try:
            ledger = eng.kv_page_ledger()
        except Exception as exc:  # noqa: BLE001 - report, don't mask
            bad.append(f"{eng.engine_id}: ledger check raised {exc!r}")
            continue
        if not ledger["ok"]:
            bad.append(f"{eng.engine_id}: {ledger}")
    return bad
