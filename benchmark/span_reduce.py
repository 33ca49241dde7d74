"""From a profiler trace (xplane) to what the HOST was doing beside the device:
the twin of ``trace_reduce.py``, which reads the device planes alone.

The program writes its hot-path spans into the profiler's host plane while a
capture is live (``dlrover_tpu/telemetry/journal.py`` ``hot_span`` /
``annotate``), so one xplane file holds them in nanoseconds of the device
trace's own clock. ``python -m benchmark.span_reduce <dir-or-xplane.pb>
<out.json>`` reads host AND device planes with ``jax.profiler.ProfileData``
(run it with ``JAX_PLATFORMS=cpu``: it must not take the chip) and writes
what :func:`reduce` returns; without ``<out.json>`` it prints the gap table
and the scopes.
The reduction is plain Python over tuples, like its twin's, so a small
recorded trace checks it without JAX.

  spans    per span name: count, wall and self seconds (a span's duration
           less the part its children on the same thread cover), and every
           event with its numeric fields and the device's busy time inside it
  steps    the ``train_step`` annotations in order: from one's start to the
           next one's start, the seconds and the device's busy time in them
  scopes   device self time per ``jax.named_scope`` of the program, from the
           operations' metadata; ``unscoped`` is the rest
  gaps     every idle gap of device 0 over 0.5 ms, with the program span that
           covers most of it: each instant of a gap goes to the innermost
           span over it, or to ``unattributed``, and the largest share names
           the gap
  idle     the gaps' sum, the part under a named span, and the sum per span

Readers (``layer_metrics/*.py``) call :func:`for_run`, which reduces a run's
trace once in a child and keeps the JSON in the run's work directory. No
per-layer metric reads ``scopes``: XLA's persistent cache keys a program
without its metadata, so an executable compiled by a build without the
scopes is served without them, and a metric would read the cache's history.
The command line prints them, for ``PERF.md`` and for the PR that moves a
scope's time (ROADMAP S2, S3).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import subprocess
import sys

from benchmark import trace_reduce

# the program's vocabulary (dlrover_tpu/telemetry/journal.py lists it)
SPAN_NAMES = (
    "train_step", "data_wait", "h2d", "dispatch", "block", "ckpt", "on_step",
    "snapshot_request", "snapshot_fetch", "snapshot_arena_write",
    "engine_step", "prefill_chunk", "kv_install", "decode_block",
    "engine_emit",
)
SCOPES = (
    "embed", "attn", "mlp", "ce_loss", "optimizer",
    "weight_cast", "kv_read", "kv_write", "lm_head", "sample",
)
STEP = "train_step"
# spans whose device time the readers ask for
DEVICE_TIME_IN = ("decode_block", "prefill_chunk")
GAP_NS = 500_000
MAX_EVENTS = 5000
OUT_NAME = "span_reduce.json"

Span = tuple  # (name, start_ns, dur_ns, thread, fields)


def merged_busy(events: list[tuple]) -> list[tuple]:
    """The union of a device's operation intervals as sorted, disjoint
    ``(start, end, first operation, last operation to end)`` tuples.
    ``events`` are ``(name, start, dur, ...)``."""
    out: list[list] = []
    for name, start, dur, *_ in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1], out[-1][3] = end, name
        else:
            out.append([start, end, name, name])
    return [tuple(b) for b in out]


def busy_within(busy: list[tuple], lo: int, hi: int) -> int:
    """Nanoseconds of ``busy`` that fall inside ``[lo, hi]``."""
    i = max(0, bisect.bisect_right(busy, (lo, 1 << 62)) - 1)
    total = 0
    for a, b, *_ in busy[i:]:
        if a >= hi:
            break
        total += max(0, min(b, hi) - max(a, lo))
    return total


def self_ns(spans: list[Span]) -> list[int]:
    """Each span's duration less the part of it that its children cover:
    spans nest on the thread that wrote them, so one stack per thread."""
    out = [0] * len(spans)
    by_thread: dict = {}
    for i, s in enumerate(spans):
        by_thread.setdefault(s[3], []).append(i)
    for idxs in by_thread.values():
        idxs.sort(key=lambda i: (spans[i][1], -spans[i][2]))
        stack: list[int] = []
        for i in idxs:
            _, start, dur, _, _ = spans[i]
            while stack and spans[stack[-1]][1] + spans[stack[-1]][2] <= start:
                stack.pop()
            out[i] = dur
            if stack:
                out[stack[-1]] -= dur
            stack.append(i)
    return [max(v, 0) for v in out]


def shares(spans: list[Span], lo: int, hi: int) -> dict[str, int]:
    """Nanoseconds of ``[lo, hi]`` by the innermost program span that
    covers each instant (of the spans over an instant, on any thread, the
    shortest); ``unattributed`` where none does."""
    over = [s for s in spans if s[1] < hi and s[1] + s[2] > lo]
    cuts = sorted({lo, hi} | {t for s in over
                              for t in (s[1], s[1] + s[2]) if lo < t < hi})
    out: dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        inner = min((s for s in over if s[1] <= a and s[1] + s[2] >= b),
                    key=lambda s: s[2], default=None)
        name = inner[0] if inner else "unattributed"
        out[name] = out.get(name, 0) + b - a
    return out


def scope_of(path: str) -> str:
    """The innermost of the program's scopes in an operation's name stack
    (``jit(_step)/transpose(jvp(ce_loss))/while/body/attn/weight_cast/
    convert_element_type:``; a transform wraps the scope it differentiates).
    The first part is the program's name, or all there is of a parameter's
    (``state.params['lm_head']:``), and names no scope."""
    for part in reversed((path or "").split("/")[1:]):
        for word in reversed(re.findall(r"[A-Za-z_]+", part)):
            if word in SCOPES:
                return word
    return "unscoped"


def reduce(spans: list[Span],
           devices: dict[str, list[tuple[str, int, int, str]]]) -> dict:
    """``spans`` are the program's host events; ``devices`` maps a device
    plane's name to its operation events ``(name, start, dur, name stack)``.
    Device numbers are those of the first device plane."""
    spans = sorted(spans, key=lambda s: s[1])
    first = devices[sorted(devices)[0]] if devices else []
    busy = merged_busy(first)
    own = self_ns(spans)
    by_name: dict[str, dict] = {}
    for s, mine in zip(spans, own):
        name, start, dur, _, fields = s
        slot = by_name.setdefault(name, {"count": 0, "wall_s": 0.0,
                                         "self_s": 0.0, "events": []})
        slot["count"] += 1
        slot["wall_s"] += dur / 1e9
        slot["self_s"] += mine / 1e9
        if len(slot["events"]) < MAX_EVENTS:
            ev = {"t_s": start / 1e9, "dur_s": dur / 1e9,
                  "self_s": mine / 1e9,
                  "fields": {k: v for k, v in fields.items()
                             if isinstance(v, (int, float))}}
            if name in DEVICE_TIME_IN and busy:
                ev["device_busy_s"] = busy_within(busy, start,
                                                  start + dur) / 1e9
            slot["events"].append(ev)

    starts = [s for s in spans if s[0] == STEP]
    steps = []
    for a, b in zip(starts, starts[1:]):
        steps.append({"step": a[4].get("step_num"),
                      "interval_s": (b[1] - a[1]) / 1e9,
                      "device_busy_s": busy_within(busy, a[1], b[1]) / 1e9})

    scopes: dict[str, float] = {}
    names = {(n, s, d): stack for n, s, d, stack in first}
    for name, (mine, _) in _self_by_event(first).items():
        scope = scope_of(names.get(name, ""))
        scopes[scope] = scopes.get(scope, 0.0) + mine / 1e9

    table = []
    by_span: dict[str, float] = {}
    for before, after in zip(busy, busy[1:]):
        lo, hi = before[1], after[0]
        if hi - lo <= GAP_NS:
            continue
        parts = shares(spans, lo, hi)
        for name, ns in parts.items():
            by_span[name] = by_span.get(name, 0.0) + ns / 1e9
        table.append({"gap_s": (hi - lo) / 1e9, "t_s": lo / 1e9,
                      "span": max(parts, key=parts.get),
                      "after": before[3], "before": after[2]})
    total = sum(g["gap_s"] for g in table)
    attributed = total - by_span.get("unattributed", 0.0)
    window = (busy[-1][1] - busy[0][0]) / 1e9 if busy else 0.0
    return {
        "devices": len(devices),
        "window_s": window,
        "busy_s": sum(b[1] - b[0] for b in busy) / 1e9,
        "spans": by_name,
        "steps": steps,
        "scopes": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
        "gaps": sorted(table, key=lambda g: -g["gap_s"]),
        "idle": {"total_s": total, "attributed_s": attributed,
                 "by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1]))},
    }


def _self_by_event(events: list[tuple]) -> dict:
    """``{(name, start, dur): [self ns, 1]}`` for one device's operations:
    the twin's self times, kept per event so each keeps its name stack."""
    keyed = [((n, s, d), s, d) for n, s, d, _ in events]
    return trace_reduce.self_times(keyed)


def gap_table(out: dict, top: int = 12) -> str:
    """The idle gaps by owner, then the longest, then the device's self
    time by the program's scopes, for ``PERF.md``."""
    idle = out["idle"]
    lines = [f"window {out['window_s']:.3f} s, busy {out['busy_s']:.3f} s; "
             f"idle in gaps over {GAP_NS / 1e6:g} ms: {idle['total_s']:.4f} s,"
             f" {idle['attributed_s']:.4f} s of it under a program span",
             "| span | gaps it names | seconds under it | longest named ms |",
             "| --- | --- | --- | --- |"]
    for span, secs in idle["by_span"].items():
        mine = [g["gap_s"] for g in out["gaps"] if g["span"] == span]
        lines.append(f"| `{span}` | {len(mine)} | {secs:.4f} | "
                     f"{1e3 * max(mine, default=0.0):.2f} |")
    lines.append("| longest gaps | owner | ms | after -> before |")
    for g in out["gaps"][:top]:
        lines.append(f"| t={g['t_s']:.4f} | `{g['span']}` | "
                     f"{1e3 * g['gap_s']:.2f} | {g['after'][:40]} -> "
                     f"{g['before'][:40]} |")
    lines.append("| scope | device self seconds | share % |")
    whole = sum(out["scopes"].values())
    for scope, secs in out["scopes"].items():
        lines.append(f"| `{scope}` | {secs:.4f} | {100 * secs / whole:.1f} |")
    if set(out["scopes"]) == {"unscoped"}:
        lines.append("no operation carries a scope: an executable from a "
                     "compile cache keeps the metadata of the build that "
                     "compiled it")
    return "\n".join(lines)


# ------------------------------------------------------- reading the file


def stats_of(ev) -> dict:
    return dict(ev.stats)


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def name_stacks(blob: bytes) -> dict[str, dict[str, str]]:
    """``{plane name: {operation's full name: its name stack}}`` of an
    xplane file. The compiler's ``op_name`` of each operation (the
    ``jax.named_scope`` path) is the ``tf_op`` stat of the operation's
    EVENT METADATA, which ``ProfileData`` does not hand out: read here
    from the file's protobuf fields (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2; map entries: key = 1, value = 2). The lines,
    which are the bulk of a file, are skipped whole."""
    out: dict[str, dict[str, str]] = {}
    for no, plane in _fields(memoryview(blob)):
        if no != 1:
            continue
        name, events, stat_names = "", [], {}
        for no, value in _fields(plane):
            if no == 2:
                name = bytes(value).decode("utf-8", "replace")
            elif no in (4, 5):
                entry = dict(_fields(value))
                if no == 5:
                    meta = dict(_fields(entry.get(2, b"")))
                    stat_names[entry.get(1, 0)] = bytes(
                        meta.get(2, b"")).decode("utf-8", "replace")
                else:
                    events.append(entry.get(2, b""))
        stacks = {}
        for event in events:
            op, stack = "", ""
            for no, value in _fields(event):
                if no == 2:
                    op = bytes(value).decode("utf-8", "replace")
                elif no == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        stack = (bytes(stat[5]).decode("utf-8", "replace")
                                 if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op and stack:
                stacks[op] = stack
        if stacks:
            out[name] = stacks
    return out


def load_xplane(path: str) -> tuple[list[Span], dict]:
    """The program's spans from the host planes, and per device plane its
    operation events with the name stack the compiler kept for each."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    with open(path, "rb") as f:
        blob = f.read()
    data = ProfileData.from_serialized_xspace(blob)
    stacks = name_stacks(blob)
    spans: list[Span] = []
    devices: dict[str, list] = {}
    for plane in data.planes:
        name = plane.name or ""
        if re.match(r"/device:(TPU|GPU):\d+$", name):
            mine = stacks.get(name, {})
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    devices.setdefault(name, []).append(
                        (trace_reduce.short_name(ev.name),
                         int(ev.start_ns), int(ev.duration_ns),
                         mine.get(ev.name, "")))
        elif name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns),
                                      f"{name}/{n}", stats_of(ev)))
    return spans, devices


def for_run(run: dict) -> dict | None:
    """The reduced trace of a run, made once in a child that holds no chip
    and kept with the run; ``None`` where the run has no trace. A reduction
    that fails is not tried again: it leaves a note with the end of its log
    on the run, which ``benchmark.run`` prints before the result line, and
    every reader gives nothing. A training run's trace is the trainer's
    profile bundle (under ``run["files"]["journal"]``), a serving run's the
    child's capture in the work directory of this process."""
    if "_span_reduce" not in run:
        run["_span_reduce"] = _reduce_once(run)
    return run["_span_reduce"]


def _reduce_once(run: dict) -> dict | None:
    from benchmark import harness

    if not run.get("trace"):
        return None
    if "files" in run:
        work = os.path.dirname(run["files"]["journal"])
        src = os.path.join(run["files"]["journal"], "bundles")
    else:
        work = next(iter(glob.glob(os.path.join(
            harness.ROOT, ".benchmark_work", f"*-{os.getpid()}"))), None)
        src = os.path.join(work, "trace") if work else None
    if not src or not glob.glob(os.path.join(src, "**", "*.xplane.pb"),
                                recursive=True):
        return None
    out = os.path.join(work, OUT_NAME)
    log = os.path.join(work, "span_reduce.log")
    if not os.path.isfile(out):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=harness.ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        with open(log, "w") as f:
            try:
                rc = subprocess.run(
                    [harness.PY, "-m", "benchmark.span_reduce", src, out],
                    cwd=harness.ROOT, env=env, stdout=f,
                    stderr=subprocess.STDOUT, timeout=300).returncode
            except subprocess.TimeoutExpired:
                rc = "timeout"
        if rc != 0 or not os.path.isfile(out):
            run.setdefault("notes", []).append(
                {"span_reduce_failed": rc, "log": harness.tail(log, 1500)})
            return None
    return harness.load_json(out)


def events_of(run: dict, span: str) -> list[dict]:
    """The events of one span name in a run's reduced trace; none where
    the run has no trace or the program wrote no such span."""
    spans = (for_run(run) or {}).get("spans") or {}
    return spans.get(span, {}).get("events", [])


def median_of(run: dict, span: str, what) -> float | None:
    """The median over a span's events of ``what(event)`` (``None`` values
    left out); ``None`` where there is nothing to read."""
    values = [v for v in map(what, events_of(run, span)) if v is not None]
    return statistics.median(values) if values else None


def main(argv=None) -> int:
    args = list(argv or sys.argv[1:])
    out = reduce(*load_xplane(args[0]))
    if len(args) > 1:
        with open(args[1], "w") as f:
            json.dump(out, f)
    else:
        print(gap_table(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
