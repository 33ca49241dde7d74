"""Render the event journal as a Perfetto-loadable job timeline.

``python -m dlrover_tpu.telemetry.timeline --journal <dir-or-file>...``
joins one or more journals (rotated ``.jsonl.1`` siblings included) into
Chrome trace-event JSON (the legacy format Perfetto's trace processor
and ui.perfetto.dev both accept):

- one ``pid`` (process track) per journal ``proc`` — i.e. one track per
  node plus one for the master — named via ``process_name`` metadata;
- one ``tid`` lane per span name inside each track (``rendezvous_wait``,
  ``compile``, ``train_step``, ``ckpt_persist``, ``ckpt_restore``, ...),
  so overlapping phases never corrupt each other's nesting;
- duration spans become ``ph="X"`` complete events; verdict-ish points
  (``hang_verdict``, ``straggler_verdict``, ``debug_bundle``,
  ``job_start``/``job_end``) and zero-duration points become ``ph="i"``
  instants;
- spans a crashed process never closed (begin without end) carry
  ``args.open=true`` — the visual signature of "died in here";
- journaled efficiency samples (``metrics_sample`` points,
  telemetry/efficiency.py) render as a ``ph="C"`` ``mfu`` counter lane,
  and the phases every ``train_step`` point carries as ``<phase>_s``
  as a stacked ``step_phase_seconds`` lane per process, so utilization
  dips line up visually with the span lanes that caused them.

Timestamps are microseconds relative to the earliest event, which keeps
the numbers small and makes the goodput report's lost-time categories
visually auditable: rendezvous storms, serial recompiles, and restore
stalls line up across node tracks.
"""

from __future__ import annotations

import argparse
import json
import sys

from dlrover_tpu.telemetry.report import Span, load_events, pair_spans

# names rendered as instants even when they carry a tiny duration
INSTANT_NAMES = frozenset({
    "hang_verdict", "straggler_verdict", "debug_bundle",
    "job_start", "job_end", "profile_request", "profile_capture",
})

# journaled metric samples render as Perfetto COUNTER tracks (ph="C"),
# not spans: metrics_sample (telemetry/efficiency.py) becomes an MFU
# lane; kv_pool (serving/observatory.py, §29) becomes page-pool,
# share-headroom and draft-acceptance lanes
COUNTER_NAMES = frozenset({"metrics_sample", "kv_pool"})
# the per-step point keeps its span lane AND feeds the stacked
# step-phase counter lane from its `<phase>_s` fields
PHASED_POINT = "train_step"


def _lane_key(span: Span) -> tuple[str, str]:
    return span.proc or "unknown", span.name


def build_trace(paths: list[str], trace: str | None = None) -> dict:
    """Trace-event JSON dict from journal paths (files or dirs)."""
    events: list[dict] = []
    for path in paths:
        events.extend(load_events(path))
    events.sort(key=lambda e: e["t"])
    spans = pair_spans(events)
    if trace:
        spans = [s for s in spans if s.trace == trace]
    counters = [s for s in spans if s.name in COUNTER_NAMES]
    spans = [s for s in spans if s.name not in COUNTER_NAMES]
    counters += [s for s in spans if s.name == PHASED_POINT and any(
        k.endswith("_s") for k in s.fields)]
    counters.sort(key=lambda s: s.end)

    procs = sorted({s.proc or "unknown" for s in spans}
                   | {s.proc or "unknown" for s in counters})
    pid_of = {proc: i + 1 for i, proc in enumerate(procs)}
    lanes = sorted({_lane_key(s) for s in spans})
    tid_of: dict[tuple[str, str], int] = {}
    for proc in procs:
        names = [name for p, name in lanes if p == proc]
        for i, name in enumerate(sorted(names)):
            tid_of[(proc, name)] = i + 1

    out: list[dict] = []
    for proc in procs:
        out.append({
            "ph": "M", "name": "process_name", "pid": pid_of[proc],
            "args": {"name": proc},
        })
        out.append({
            "ph": "M", "name": "process_sort_index", "pid": pid_of[proc],
            "args": {"sort_index": pid_of[proc]},
        })
    for (proc, name), tid in sorted(tid_of.items()):
        out.append({
            "ph": "M", "name": "thread_name", "pid": pid_of[proc],
            "tid": tid, "args": {"name": name},
        })

    t0 = min(
        (s.start for s in spans + counters), default=0.0
    ) if spans or counters else 0.0
    for span in spans:
        proc = span.proc or "unknown"
        pid, tid = pid_of[proc], tid_of[(proc, span.name)]
        args = dict(span.fields)
        args["span_id"] = span.span_id
        if span.parent:
            args["parent"] = span.parent
        if span.open:
            args["open"] = True
        ts = round((span.start - t0) * 1e6, 3)
        dur = round((span.end - span.start) * 1e6, 3)
        if span.name in INSTANT_NAMES or dur <= 0:
            out.append({
                "ph": "i", "name": span.name, "cat": "verdict"
                if span.name in INSTANT_NAMES else "point",
                # instants mark the moment they were EMITTED (span.start
                # backdates points by their dur)
                "ts": round((span.end - t0) * 1e6, 3),
                "pid": pid, "tid": tid, "s": "t", "args": args,
            })
        else:
            out.append({
                "ph": "X", "name": span.name, "cat": span.name,
                "ts": ts, "dur": dur, "pid": pid, "tid": tid,
                "args": args,
            })

    # flow events (ph="s"/"f"): a causal arrow from each parent span to
    # every child in a DIFFERENT lane (same-lane nesting already reads
    # visually), so cross-process trees — RPC caller -> servicer
    # handler, gateway request -> prefill/decode, incident -> trainer
    # restore — render as arrows in Perfetto (DESIGN.md §27)
    by_id = {s.span_id: s for s in spans if s.span_id}

    def _is_slice(s: Span) -> bool:
        return s.name not in INSTANT_NAMES and s.end > s.start

    for span in spans:
        parent = by_id.get(span.parent) if span.parent else None
        if parent is None or not _is_slice(parent) or not _is_slice(span):
            continue
        if _lane_key(parent) == _lane_key(span):
            continue
        try:
            flow_id = int(span.span_id, 16) & 0x7FFFFFFF
        except ValueError:
            continue
        p_proc = parent.proc or "unknown"
        # step ts must land inside the slice it binds to
        s_ts = min(max(span.start, parent.start), parent.end)
        out.append({
            "ph": "s", "name": "causal", "cat": "flow", "id": flow_id,
            "ts": round((s_ts - t0) * 1e6, 3),
            "pid": pid_of[p_proc], "tid": tid_of[(p_proc, parent.name)],
        })
        c_proc = span.proc or "unknown"
        out.append({
            "ph": "f", "name": "causal", "cat": "flow", "id": flow_id,
            "bp": "e",
            "ts": round((span.start - t0) * 1e6, 3),
            "pid": pid_of[c_proc], "tid": tid_of[(c_proc, span.name)],
        })

    # counter tracks: MFU lane + stacked step-phase lane per process,
    # so the efficiency series read alongside the span lanes
    for sample in counters:
        proc = sample.proc or "unknown"
        pid = pid_of[proc]
        ts = round((sample.end - t0) * 1e6, 3)
        if sample.name == "kv_pool":
            # §29 serving-observatory lanes: stacked free/used pages,
            # COW share headroom, and the shadow acceptance rate
            out.append({
                "ph": "C", "name": "kv_pages", "cat": "serving",
                "ts": ts, "pid": pid, "args": {
                    "used": float(sample.fields.get("used", 0) or 0),
                    "free": float(sample.fields.get("free", 0) or 0),
                },
            })
            out.append({
                "ph": "C", "name": "kv_shareable_frac",
                "cat": "serving", "ts": ts, "pid": pid, "args": {
                    "shareable_frac": float(
                        sample.fields.get("shareable_frac", 0.0) or 0),
                },
            })
            out.append({
                "ph": "C", "name": "draft_accept_rate",
                "cat": "serving", "ts": ts, "pid": pid, "args": {
                    "accept_rate": float(
                        sample.fields.get("accept_rate", 0.0) or 0),
                },
            })
            continue
        mfu = sample.fields.get("mfu")
        if isinstance(mfu, (int, float)):
            out.append({
                "ph": "C", "name": "mfu", "cat": "efficiency",
                "ts": ts, "pid": pid, "args": {"mfu": float(mfu)},
            })
        phases = {
            k[:-2]: float(v) for k, v in sorted(sample.fields.items())
            if sample.name == PHASED_POINT and k.endswith("_s")
            and isinstance(v, (int, float))
        }
        if phases:
            out.append({
                "ph": "C", "name": "step_phase_seconds",
                "cat": "efficiency", "ts": ts, "pid": pid,
                "args": phases,
            })

    traces = sorted(
        {s.trace for s in spans if s.trace}
        | {s.trace for s in counters if s.trace}
    )
    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "dlrover_tpu.telemetry.timeline",
            "traces": traces,
            "epoch_t0": t0,
            "n_spans": len(spans),
            "n_counter_samples": len(counters),
        },
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        "python -m dlrover_tpu.telemetry.timeline",
        description="journal -> Chrome trace-event JSON (open in "
                    "ui.perfetto.dev or chrome://tracing)",
    )
    parser.add_argument("--journal", required=True, nargs="+",
                        help="journal file(s) or DLROVER_TPU_JOURNAL_DIR "
                             "dir(s); rotated .1 siblings are included")
    parser.add_argument("--trace", default=None,
                        help="restrict to one trace id")
    parser.add_argument("--out", default="",
                        help="output path (default: stdout)")
    parser.add_argument("--indent", type=int, default=None,
                        help="pretty-print with this indent")
    args = parser.parse_args(argv)
    trace = build_trace(args.journal, trace=args.trace)
    text = json.dumps(trace, indent=args.indent)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {len(trace['traceEvents'])} trace events "
              f"({trace['otherData']['n_spans']} spans) to {args.out}",
              file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
