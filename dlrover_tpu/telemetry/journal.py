"""Crash-safe cross-process event journal (JSONL spans).

Every framework process (master, agents, trainers, serving) appends
single-line JSON events to one shared file under
``DLROVER_TPU_JOURNAL_DIR``. Appends use ``O_APPEND`` with one short
``os.write`` per line, so concurrent writers interleave at line
granularity and a SIGKILL loses at most its own final line — the same
durability contract as ``utils/goodput.py``'s recorder.

Span model: ``trace_id`` identifies the job (minted by the master at
start, propagated to agents in the rendezvous payload and to trainers
via ``DLROVER_TPU_TRACE_ID`` in the child env); ``span``/``parent``
link events into trees across processes. Events are ``b`` (begin),
``e`` (end, carries ``dur``), or ``p`` (point, optional ``dur`` for a
completed interval recorded in one line). A begin with no matching end
means the process died inside the span — the offline report treats it
as open until the journal's last event.

Span context (DESIGN.md §27): a context-local span stack makes nested
``span(...)`` blocks parent their children automatically, and a
``trace:span`` context string (``current_ctx()`` / ``parse_ctx()``)
carries causality across process boundaries — in the RPC envelope
(``common/rpc.py`` ``sctx`` key, adopted server-side via
``adopt_remote_ctx``), in message payloads (``sctx`` fields), and in
the child environment (``DLROVER_TPU_SPAN_CTX``, read back with
``spawn_ctx()``). ``remote_parent=`` accepts such a context string and
is used as the parent only when no local span is on the stack — local
causality wins. Under ``DLROVER_TPU_TRACE_SEED`` span ids come from a
deterministic per-process counter stream instead of ``uuid4``, so
seeded chaos/fleetsim replays produce byte-identical trace trees.
``telemetry/trace.py`` assembles the journals of all nodes into causal
trees with critical paths.

Span taxonomy (names are load-bearing for ``telemetry/report.py`` and
``telemetry/timeline.py``; ``native/check_metric_names.py`` lints that
every name is documented in DESIGN.md): ``rdzv_round`` / ``job_start`` /
``job_end`` / ``straggler_verdict`` / ``snapshot_interval_retune``
(master), ``rendezvous_wait`` / ``node_restart`` / ``ckpt_persist`` /
``hang_verdict`` / ``debug_bundle`` / ``standby_promote`` /
``profile_request`` (agent), ``compile`` / ``train_step`` /
``ckpt_restore`` / ``restore_prefetch`` / ``metrics_sample`` /
``profile_capture`` (trainer), ``gateway_*`` (serving gateway).

Hot-path spans (``hot_span`` / ``annotate`` below; DESIGN.md §32): the
trainer loop's phases ``data_wait`` / ``h2d`` / ``dispatch`` / ``block``
/ ``ckpt`` and the caller's ``on_step`` callback (profiler annotations
in and around the ``train_step`` step annotation; the per-step
``train_step`` journal point carries the phases as ``*_s`` fields), the
snapshot path ``snapshot_request`` -> ``snapshot_fetch`` /
``snapshot_arena_write`` (the writer thread's children of the request),
and the serving engine's ``engine_step`` -> ``prefill_chunk`` /
``kv_install`` / ``decode_block`` / ``engine_emit``.
``hot_span`` writes each to the journal AND, while a ``jax.profiler``
capture is live, into the profiler's host plane under the same name and
fields — the xplane file then holds the program's spans in nanoseconds
of the device trace's own clock, so a device idle gap can be put down
to what the host was doing (``benchmark/span_reduce.py``).

Rotation: when ``DLROVER_TPU_JOURNAL_MAX_MB`` is set, a file that
reaches the cap is atomically renamed to ``.1`` (replacing the previous
one) and reopened, bounding a long soak's footprint at ~2x the cap;
``report``/``timeline`` read the rotated sibling transparently.
"""

from __future__ import annotations

import contextvars
import hashlib
import itertools
import json
import os
import sys
import time
import uuid
from contextlib import contextmanager
from typing import Iterator, Optional

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.telemetry.metrics import registry

JOURNAL_FILE = "events.jsonl"
ROTATED_SUFFIX = ".1"

_spans_total = registry().counter(
    "dlrover_tpu_trace_spans_total",
    "journal trace events written, by event kind (b/e/p)",
    ("kind",),
)
_dropped_total = registry().counter(
    "dlrover_tpu_trace_dropped_total",
    "per-request trace roots dropped by head sampling",
)


def max_journal_bytes() -> int:
    """Size cap from ``DLROVER_TPU_JOURNAL_MAX_MB`` (0/unset = unbounded)."""
    raw = os.environ.get(EnvKey.JOURNAL_MAX_MB, "").strip()
    if not raw:
        return 0
    try:
        return max(0, int(float(raw) * (1 << 20)))
    except ValueError:
        return 0


def mint_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def current_trace_id() -> str:
    return os.environ.get(EnvKey.TRACE_ID, "")


def set_trace_id(trace_id: str) -> None:
    """Adopt a trace id (agents call this with the rendezvous payload's
    id; children inherit it through the environment)."""
    if trace_id:
        os.environ[EnvKey.TRACE_ID] = trace_id


def _proc_name() -> str:
    node = os.environ.get(EnvKey.NODE_ID)
    if node is None:
        return f"pid{os.getpid()}"
    return f"node{node}"


# ------------------------------------------------------------- span context
#
# A context string is ``"<trace_id>:<span_id>"`` — the wire format every
# propagation point uses (RPC envelope ``sctx`` key, message ``sctx``
# fields, ``DLROVER_TPU_SPAN_CTX`` in a child env, standby promotion
# payloads, ``KVBundle.sctx``).

_SPAN_STACK: contextvars.ContextVar[tuple[str, ...]] = \
    contextvars.ContextVar("dlrover_tpu_span_stack", default=())
# Deterministic-id counters, one stream per span NAME (used only under
# DLROVER_TPU_TRACE_SEED). A single global counter would make ids
# depend on how concurrent threads interleave their draws — a heartbeat
# emitting between two recovery spans would shift every later id and
# break replay determinism. Per-name streams are immune to cross-name
# interleaving; same-name spans racing within one process swap ids only
# among themselves, which the skeleton contract cannot observe.
_SPAN_SEQ: dict[str, Iterator[int]] = {}


def format_ctx(trace: str, span: str) -> str:
    return f"{trace}:{span}" if span else ""


def parse_ctx(ctx: str | None) -> tuple[str, str]:
    if not ctx or not isinstance(ctx, str):
        return "", ""
    trace, _, span = ctx.rpartition(":")
    return trace, span


def mint_span_id(name: str = "") -> str:
    """A fresh span id. Random (``uuid4``) normally; under
    ``DLROVER_TPU_TRACE_SEED`` a deterministic blake2s stream keyed by
    (seed, namespace, node, incarnation, standby-ness, rank, span name,
    per-name counter: the namespace — ``DLROVER_TPU_SPAN_NS`` —
    separates co-located processes that share every other component,
    e.g. the standalone master and the agent that spawned it), so the
    same seeded chaos/fleetsim run always mints the same ids — trace
    trees stay byte-identical across replays."""
    seed = os.environ.get(EnvKey.TRACE_SEED, "")
    if not seed:
        return uuid.uuid4().hex[:12]
    stream = "|".join((
        seed,
        os.environ.get(EnvKey.SPAN_NS, "-"),
        os.environ.get(EnvKey.NODE_ID, "m"),
        os.environ.get(EnvKey.RESTART_COUNT, "-"),
        "s" if os.environ.get(EnvKey.STANDBY_FILE) else "-",
        os.environ.get(EnvKey.GLOBAL_RANK, "-"),
        name,
        str(next(_SPAN_SEQ.setdefault(name, itertools.count()))),
    ))
    return hashlib.blake2s(stream.encode(), digest_size=6).hexdigest()


def current_span_id() -> str:
    """Innermost live span in this execution context ("" if none)."""
    stack = _SPAN_STACK.get()
    return stack[-1] if stack else ""


def current_ctx() -> str:
    """The ``trace:span`` context string a caller puts on the wire so
    the remote side journals as a child ("" when no span is live)."""
    return format_ctx(current_trace_id(), current_span_id())


def spawn_ctx() -> str:
    """The spawn-time span context a parent process left in the child's
    environment (``DLROVER_TPU_SPAN_CTX``) — recovery call sites pass
    it as ``remote_parent=`` so restore/recompile attach under the
    incident that respawned them."""
    return os.environ.get(EnvKey.SPAN_CTX, "")


@contextmanager
def adopt_remote_ctx(ctx: str | None) -> Iterator[None]:
    """Adopt a remote caller's span context for the duration of a block
    (the RPC server wraps handler dispatch in this), so every journal
    emission inside attaches as a child of the caller's span."""
    _, span = parse_ctx(ctx)
    if not span:
        yield
        return
    token = _SPAN_STACK.set(_SPAN_STACK.get() + (span,))
    try:
        yield
    finally:
        _SPAN_STACK.reset(token)


def should_sample(key: str) -> bool:
    """Head-sampling decision for per-request serving traces, stable in
    the request id so every hop of one request agrees. Incidents and
    control-plane traces never consult this — they are always sampled."""
    raw = os.environ.get(EnvKey.TRACE_SAMPLE, "").strip()
    if not raw:
        return True
    try:
        rate = float(raw)
    except ValueError:
        return True
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        _dropped_total.inc()
        return False
    h = int.from_bytes(hashlib.blake2s(key.encode(),
                                       digest_size=4).digest(), "big")
    if h / 0xFFFFFFFF < rate:
        return True
    _dropped_total.inc()
    return False


class EventJournal:
    def __init__(self, path: str, proc: str | None = None,
                 trace_id: str | None = None):
        self._path = path
        self._proc = proc or _proc_name()
        self._trace = trace_id  # None -> read the env per event
        self._max_bytes = max_journal_bytes()
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._fd = os.open(path, os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                           0o644)

    @property
    def enabled(self) -> bool:
        return True

    @property
    def path(self) -> str:
        return self._path

    def _maybe_rotate(self) -> None:
        """Size-capped rotation (``DLROVER_TPU_JOURNAL_MAX_MB``): rename
        the full file to ``.1`` (replacing the previous ``.1``) and
        reopen, so a long soak holds at most ~2x the cap on disk.

        Crash-safety is preserved: writes stay single short ``O_APPEND``
        appends and the rename is atomic. With several writer processes
        on one file, only the writer whose fd still IS the live file
        performs the rename — a writer that lost the race (its fd now
        points at the rotated file) just reopens the fresh one.
        """
        if self._max_bytes <= 0:
            return
        st = os.fstat(self._fd)
        if st.st_size < self._max_bytes:
            return
        try:
            live_ino = os.stat(self._path).st_ino
        except FileNotFoundError:
            live_ino = -1
        if live_ino == st.st_ino:
            os.replace(self._path, self._path + ROTATED_SUFFIX)
        os.close(self._fd)
        self._fd = os.open(self._path,
                           os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)

    def _write(self, event: dict) -> None:
        try:
            self._maybe_rotate()
            os.write(self._fd,
                     (json.dumps(event, separators=(",", ":")) + "\n")
                     .encode("utf-8"))
            _spans_total.labels(event.get("ev", "p")).inc()
        except OSError:
            pass  # telemetry must never take down the instrumented path

    @staticmethod
    def _resolve_parent(parent: str | None,
                        remote_parent: str | None) -> str | None:
        """Parent precedence: explicit ``parent`` span id, then the
        innermost local span on the context stack, then the span half of
        a ``remote_parent`` context string — local causality wins over a
        remote link."""
        if parent:
            return parent
        local = current_span_id()
        if local:
            return local
        if remote_parent:
            return parse_ctx(remote_parent)[1] or None
        return None

    def _base(self, name: str, ev: str, span_id: str,
              parent: str | None, fields: dict) -> dict:
        event = {
            "t": time.time(),
            "trace": self._trace if self._trace is not None
            else current_trace_id(),
            "span": span_id,
            "name": name,
            "ev": ev,
            "proc": self._proc,
            "pid": os.getpid(),
        }
        if parent:
            event["parent"] = parent
        event.update(fields)
        return event

    def emit(self, name: str, parent: str | None = None,
             dur: float | None = None, remote_parent: str | None = None,
             span_id: str | None = None, **fields) -> str:
        """One-line point event; ``dur`` marks a completed interval that
        ended at the event's timestamp. ``span_id`` lets a caller that
        pre-minted an id (so other processes could attach children
        before this retroactive point is written) reuse it."""
        span_id = span_id or mint_span_id(name)
        if dur is not None:
            fields["dur"] = round(float(dur), 6)
        parent = self._resolve_parent(parent, remote_parent)
        self._write(self._base(name, "p", span_id, parent, fields))
        return span_id

    def begin(self, name: str, parent: str | None = None,
              remote_parent: str | None = None, **fields) -> str:
        span_id = mint_span_id(name)
        parent = self._resolve_parent(parent, remote_parent)
        self._write(self._base(name, "b", span_id, parent, fields))
        return span_id

    def end(self, span_id: str, name: str, start: float | None = None,
            **fields) -> None:
        if start is not None:
            fields["dur"] = round(time.time() - start, 6)
        self._write(self._base(name, "e", span_id, None, fields))

    @contextmanager
    def span(self, name: str, parent: str | None = None,
             remote_parent: str | None = None,
             **fields) -> Iterator[str]:
        start = time.time()
        span_id = self.begin(name, parent=parent,
                             remote_parent=remote_parent, **fields)
        token = _SPAN_STACK.set(_SPAN_STACK.get() + (span_id,))
        try:
            yield span_id
        finally:
            _SPAN_STACK.reset(token)
            self.end(span_id, name, start=start)

    def close(self) -> None:
        try:
            os.close(self._fd)
        except OSError:
            pass


class NullJournal:
    """API-compatible no-op used when journaling is not configured."""

    enabled = False
    path = ""

    def emit(self, name: str, parent: str | None = None,
             dur: float | None = None, remote_parent: str | None = None,
             span_id: str | None = None, **fields) -> str:
        return ""

    def begin(self, name: str, parent: str | None = None,
              remote_parent: str | None = None, **fields) -> str:
        return ""

    def end(self, span_id: str, name: str, start: float | None = None,
            **fields) -> None:
        pass

    @contextmanager
    def span(self, name: str, parent: str | None = None,
             remote_parent: str | None = None,
             **fields) -> Iterator[str]:
        yield ""

    def close(self) -> None:
        pass


_cached: Optional[tuple[str, int, object]] = None


def get_journal():
    """The process journal: a real one when ``DLROVER_TPU_JOURNAL_DIR``
    is set, else a no-op. Cached per (dir, pid) so forked children get
    their own fd."""
    global _cached
    journal_dir = os.environ.get(EnvKey.JOURNAL_DIR, "")
    pid = os.getpid()
    if _cached is not None and _cached[0] == journal_dir \
            and _cached[1] == pid:
        return _cached[2]
    if not journal_dir:
        journal: object = NullJournal()
    else:
        try:
            journal = EventJournal(os.path.join(journal_dir, JOURNAL_FILE))
        except OSError:
            journal = NullJournal()
    _cached = (journal_dir, pid, journal)
    return journal


# ------------------------------------------------------- hot-path spans
#
# The one place a journal span is paired with a profiler annotation.


class _NoAnnotation:
    """Stands in where no profiler capture can be live."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **fields) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()


def annotate(name: str, step_num: int | None = None, **fields):
    """A ``jax.profiler.TraceAnnotation`` of this name with ``fields``
    as its arguments: the profiler half of :func:`hot_span`, for
    intervals too frequent to journal (the trainer's step phases).
    With ``step_num`` it is a ``StepTraceAnnotation``, which the
    profiler's own tools read as one training step. A no-op object
    unless a capture is live in this process. JAX is never imported
    here: a process that has not imported it (master, agent) cannot be
    under capture."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return _NO_ANNOTATION
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                                **fields)
    return jax.profiler.TraceAnnotation(name, **fields)


class HotSpan:
    """Handle a ``hot_span`` block yields: ``id`` is the journal span id
    ("" under ``NullJournal``), ``set`` adds fields known only once the
    work is under way (they land on the journal's end event and on the
    profiler event)."""

    __slots__ = ("id", "_annotation", "_late")

    def __init__(self, span_id: str, annotation) -> None:
        self.id = span_id
        self._annotation = annotation
        self._late: dict = {}

    def set(self, **fields) -> None:
        self._late.update(fields)
        self._annotation.set_metadata(**fields)


@contextmanager
def hot_span(name: str, remote_parent: str | None = None,
             **fields) -> Iterator[HotSpan]:
    """A journal span and a profiler annotation of the same name and
    fields over one block. With no capture live the annotation is a
    no-op; with no journal directory the journal half is
    ``NullJournal``: then nothing is written anywhere. ``remote_parent``
    is a serving request's span context string: where it names a span,
    the journal span is a child of the REQUEST, whatever encloses it
    here (a request's chunk runs inside some engine step, but belongs
    to the request's tree; in the profiler's plane nesting is by time
    anyway)."""
    journal = get_journal()
    start = time.time()
    with annotate(name, **fields) as annotation:
        span_id = journal.begin(
            name, parent=parse_ctx(remote_parent)[1] or None, **fields)
        span = HotSpan(span_id, annotation)
        token = _SPAN_STACK.set(_SPAN_STACK.get() + (span_id,)) \
            if span_id else None
        try:
            yield span
        finally:
            if token is not None:
                _SPAN_STACK.reset(token)
            journal.end(span_id, name, start=start, **span._late)

