"""Offline lost-time attribution: join the event journal with goodput.

``python -m dlrover_tpu.telemetry.report --journal <dir-or-file>
[--goodput-log <jsonl>]`` prints where the wall-clock went: the total
lost time comes from ``utils/goodput.py``'s accounting (total −
productive over the warm window), and the journal's spans attribute it
by cause — respawn vs rendezvous vs restore vs recompile vs redone —
with the remainder reported as unattributed. The category names are
ONE vocabulary with the bench's per-failure phase breakdown
(``bench.py`` emits ``goodput_*_{respawn,rendezvous,restore,recompile,
redone}_s`` from the same journal), so the offline report and the
bench artifact always agree on what a phase is called.

Attribution is interval-union based: per category, the spans from every
process are merged into disjoint intervals and clipped to the goodput
warm window, so two agents re-rendezvousing concurrently count the
stall once, the way the job experienced it. Beyond the job-wide
totals, the report attributes the same phases **per incarnation**
(windows between ``node_restart`` spans, keyed by their journaled
incarnation number), so a single slow recovery is visible instead of
averaged away.

Beside the lost-time table the report renders a **steady-state
efficiency** table (DESIGN.md §18) from the trainer's journaled
``metrics_sample`` and ``train_step`` points: per-incarnation MFU,
mean step time, %-of-samples host-blocked, and the phase breakdown —
"where does a healthy step go" next to "where did the failures' time
go" — and a **master saturation** table (DESIGN.md §22) from the
``master_rpc`` points a real master emits at stop and the fleet
simulator emits per run: per node-count tier, the dominant
control-plane cost center with per-center totals and p99s. ``--format
json`` emits the whole report as one stable-keyed document for
bench/CI consumption.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Iterable, Optional

from dlrover_tpu.utils.goodput import GoodputReport, compute_goodput

# span name -> lost-time category (journal.py documents the taxonomy).
# restore_prefetch is deliberately absent: an overlapped prefetch runs
# concurrently with rendezvous/compile, OFF the critical path — charging
# it as lost time would double-count the phases it hides behind.
CATEGORY_OF = {
    "rdzv_round": "rendezvous",
    "rendezvous_wait": "rendezvous",
    "node_restart": "respawn",
    "compile": "recompile",
    # under the elastic compile cache (DESIGN.md §17) the XLA compile —
    # or its ~0.1s cached-executable load — happens inside
    # load_or_compile BEFORE the first dispatch; this event carries
    # that cost, while "compile" keeps the (now small) first-step time
    "compile_cache": "recompile",
    "ckpt_restore": "restore",
}
# one vocabulary with bench.py's per-failure phase breakdown
CATEGORIES = ("respawn", "rendezvous", "restore", "recompile", "redone")
# recompile splits on the cache outcome (elastic compile cache,
# DESIGN.md §17): warm = the executable was served from the cache (the
# interval is a ~0.1s load), cold = a real XLA compile. The flag field
# is "hit" on compile_cache events and "cache_hit" on first-dispatch
# compile events; events from before the cache (no flag) count as cold
# — that is what they were. The subcategories tile the parent:
# recompile == recompile_warm + recompile_cold (up to interval overlap).
RECOMPILE_SUBCATEGORIES = ("recompile_warm", "recompile_cold")


def _recompile_sub(span: "Span") -> str:
    hit = (span.fields.get("hit") if span.name == "compile_cache"
           else span.fields.get("cache_hit"))
    return "recompile_warm" if hit else "recompile_cold"


def load_events(path: str) -> list[dict]:
    """Parse one journal file, or every ``*.jsonl`` in a directory.

    Rotated siblings (``*.jsonl.1``, see ``journal.py`` size-capped
    rotation) are read transparently — before the live file, so spans
    split across a rotation reassemble in time order.
    """
    files: list[str] = []
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.endswith(".jsonl") or f.endswith(".jsonl.1")
        )
    elif os.path.exists(path) or os.path.exists(path + ".1"):
        files = [p for p in (path + ".1", path) if os.path.exists(p)]
    events: list[dict] = []
    for fp in files:
        with open(fp) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn final line after a SIGKILL
                if isinstance(ev, dict) and "t" in ev and "name" in ev:
                    events.append(ev)
    events.sort(key=lambda e: e["t"])
    return events


@dataclasses.dataclass
class Span:
    span_id: str
    name: str
    proc: str
    trace: str
    start: float
    end: float
    parent: str = ""
    open: bool = False  # begin with no end: the process died inside
    fields: dict = dataclasses.field(default_factory=dict)


def pair_spans(events: list[dict]) -> list[Span]:
    """Reassemble spans from b/e/p lines; an unmatched begin is closed at
    the journal's final timestamp (crash semantics).

    Rotation accounting: a span whose begin/end straddle the ``.1``
    rotation boundary pairs normally, because ``load_events`` reads the
    rotated sibling before the live file and matching is by span id.
    When the begin has aged out entirely (rotated past ``.1`` and
    deleted), the orphan end still carries the ``dur`` the writer
    stamped (``journal.end(..., start=t0)``), so the span is
    reconstructed from the end line alone — attributed exactly once,
    never dropped, never double-counted (the reconstruction only
    happens when no begin matched).
    """
    if not events:
        return []
    last_t = events[-1]["t"]
    meta = {"t", "trace", "span", "name", "ev", "proc", "pid", "parent",
            "dur"}
    spans: list[Span] = []
    open_spans: dict[str, Span] = {}
    for ev in events:
        kind = ev.get("ev")
        fields = {k: v for k, v in ev.items() if k not in meta}
        if kind == "b":
            span = Span(
                span_id=ev.get("span", ""), name=ev["name"],
                proc=ev.get("proc", ""), trace=ev.get("trace", ""),
                start=ev["t"], end=last_t, parent=ev.get("parent", ""),
                open=True, fields=fields,
            )
            open_spans[span.span_id] = span
            spans.append(span)
        elif kind == "e":
            span = open_spans.pop(ev.get("span", ""), None)
            if span is not None:
                span.end = ev["t"]
                span.open = False
                span.fields.update(fields)
            else:
                # begin rotated past .1: rebuild from the end's dur
                dur = float(ev.get("dur", 0.0) or 0.0)
                fields["begin_rotated"] = True
                spans.append(Span(
                    span_id=ev.get("span", ""), name=ev["name"],
                    proc=ev.get("proc", ""), trace=ev.get("trace", ""),
                    start=ev["t"] - dur, end=ev["t"],
                    parent=ev.get("parent", ""), fields=fields,
                ))
        else:  # point
            dur = float(ev.get("dur", 0.0) or 0.0)
            spans.append(Span(
                span_id=ev.get("span", ""), name=ev["name"],
                proc=ev.get("proc", ""), trace=ev.get("trace", ""),
                start=ev["t"] - dur, end=ev["t"],
                parent=ev.get("parent", ""), fields=fields,
            ))
    return spans


def _union_seconds(intervals: Iterable[tuple[float, float]],
                   window: tuple[float, float] | None = None) -> float:
    clipped = []
    for start, end in intervals:
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        if end > start:
            clipped.append((start, end))
    total = 0.0
    cur_s = cur_e = None
    for start, end in sorted(clipped):
        if cur_e is None or start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = start, end
        else:
            cur_e = max(cur_e, end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class LostTimeReport:
    total_s: float
    productive_s: float
    lost_s: float
    goodput: float
    categories: dict[str, float]
    unattributed_s: float
    n_spans: int
    traces: list[str]
    goodput_report: Optional[GoodputReport] = None
    # per-incarnation rows, bench's phase vocabulary:
    # {"incarnation": k, "respawn_s": ..., "rendezvous_s": ...,
    #  "restore_s": ..., "recompile_s": ..., "redone_steps": ...,
    #  "redone_s": ...}
    incarnations: list[dict] = dataclasses.field(default_factory=list)
    # steady-state efficiency rows per incarnation, from the trainer's
    # journaled metrics_sample and train_step points
    # (telemetry/efficiency.py): {"incarnation", "samples", "mfu_mean",
    # "mfu_min", "mfu_max", "step_s_mean", "host_blocked_pct",
    # "phase_s": {phase: mean seconds}, "phase_pct": {phase: share}}
    efficiency: list[dict] = dataclasses.field(default_factory=list)
    # master control-plane saturation per node-count tier (DESIGN.md
    # §22), from the ``master_rpc`` points a real master emits at stop
    # and the fleet simulator emits per run: {"nodes", "dominant",
    # "dominant_total_ms", "total_ms": {center: ms}, "rpc_p99_ms":
    # {center: ms}} — centers are RPC types, ``lock/<structure>``
    # waits, and ``snapshot_ingest``
    master_saturation: list[dict] = dataclasses.field(
        default_factory=list
    )
    # serving memory observatory per engine process (DESIGN.md §29),
    # from periodic ``kv_pool`` journal samples: {"proc", "samples",
    # "kv_pages_total", "kv_occupancy_mean", "kv_occupancy_p95",
    # "kv_pages_high_water", "pages_shareable_frac", "cow_multiplier",
    # "draft_accept_rate", "tokens_scored", "accept_run_p50",
    # "accept_run_p95"} — the measured headroom for ROADMAP-3's COW
    # and speculative-decoding levers
    serving_observatory: list[dict] = dataclasses.field(
        default_factory=list
    )

    def to_dict(self) -> dict:
        d = {
            "total_s": round(self.total_s, 4),
            "productive_s": round(self.productive_s, 4),
            "lost_s": round(self.lost_s, 4),
            "goodput": round(self.goodput, 4),
            "categories": {k: round(v, 4)
                           for k, v in self.categories.items()},
            "unattributed_s": round(self.unattributed_s, 4),
            "n_spans": self.n_spans,
            "traces": self.traces,
            "incarnations": self.incarnations,
            "efficiency": self.efficiency,
            "master_saturation": self.master_saturation,
            "serving_observatory": self.serving_observatory,
        }
        if self.goodput_report is not None:
            d["goodput_report"] = self.goodput_report.to_dict()
        return d


def build_report(journal_path: str, goodput_log: str | None = None,
                 end_time: float | None = None,
                 trace: str | None = None) -> LostTimeReport:
    events = load_events(journal_path)
    spans = pair_spans(events)
    if trace:
        spans = [s for s in spans if s.trace == trace]
    traces = sorted({s.trace for s in spans if s.trace})

    greport: GoodputReport | None = None
    window: tuple[float, float] | None = None
    median = 0.0
    if goodput_log:
        greport = compute_goodput(goodput_log, end_time=end_time)
        median = greport.median_step_s
        # reconstruct the warm window's absolute bounds: compute_goodput
        # measures total_s back from the log's final event (or end_time)
        from dlrover_tpu.utils.goodput import _parse_events

        gevents = _parse_events(goodput_log)
        t_end = gevents[-1]["t"]
        if end_time is not None:
            t_end = max(t_end, end_time)
        window = (t_end - greport.total_s, t_end)

    by_cat: dict[str, list[tuple[float, float]]] = {}
    for span in spans:
        cat = CATEGORY_OF.get(span.name)
        if cat is None:
            continue
        start, end = span.start, span.end
        if span.name == "compile" and median > 0:
            # older journals' "compile" events timed the whole first
            # step (compute included); current trainers emit the
            # pre-block dispatch wall. Netting a steady median (clamped
            # at zero) corrects the former and at most trims one step
            # off a real compile for the latter — conservative either
            # way: the step's own compute is training, not lost time
            end = max(start, end - median)
        by_cat.setdefault(cat, []).append((start, end))
        if cat == "recompile":
            by_cat.setdefault(_recompile_sub(span), []).append(
                (start, end))

    categories = {
        cat: _union_seconds(by_cat.get(cat, ()), window)
        for cat in CATEGORIES + RECOMPILE_SUBCATEGORIES
        if cat != "redone"
    }
    categories["redone"] = (
        greport.redone_steps * median if greport is not None else 0.0
    )

    if greport is not None:
        total, productive = greport.total_s, greport.productive_s
        lost, goodput = greport.lost_s, greport.goodput
    else:
        # journal-only mode: no productive-time accounting, so "lost" is
        # just the union of everything the journal attributes
        all_intervals = [iv for ivs in by_cat.values() for iv in ivs]
        lost = _union_seconds(all_intervals, window)
        total, productive, goodput = lost, 0.0, 0.0

    attributed = _union_seconds(
        [iv for ivs in by_cat.values() for iv in ivs], window
    ) + categories["redone"]
    return LostTimeReport(
        total_s=total,
        productive_s=productive,
        lost_s=lost,
        goodput=goodput,
        categories=categories,
        unattributed_s=max(0.0, lost - attributed),
        n_spans=len(spans),
        traces=traces,
        goodput_report=greport,
        incarnations=_per_incarnation(
            spans, window, median,
            goodput_log if greport is not None else None,
        ),
        efficiency=_efficiency_rows(spans),
        master_saturation=_master_saturation_rows(spans),
        serving_observatory=_serving_observatory_rows(spans),
    )


def _redone_by_incarnation(goodput_log: str) -> dict[int, int]:
    """Steps re-run per incarnation: an incarnation whose first step is
    at or below the previous incarnations' high-water mark is redoing
    rolled-back work until it passes it."""
    from dlrover_tpu.utils.goodput import _parse_events

    redone: dict[int, int] = {}
    cur_inc = 0
    max_step = 0
    first_step_pending = False
    for ev in _parse_events(goodput_log):
        kind = ev.get("ev")
        if kind == "start":
            cur_inc = int(ev.get("restart", 0) or 0)
            first_step_pending = True
        elif kind == "step":
            step = int(ev.get("step", 0) or 0)
            if first_step_pending:
                first_step_pending = False
                if max_step and step <= max_step:
                    redone[cur_inc] = (
                        redone.get(cur_inc, 0) + max_step - step + 1
                    )
            max_step = max(max_step, step)
    return redone


def _incarnation_bounds(spans: list[Span]) -> list[tuple[int, float]]:
    """(incarnation, window_start) bins from ``node_restart`` spans;
    incarnation 0 runs from the beginning."""
    restarts = sorted(
        (s for s in spans if s.name == "node_restart"),
        key=lambda s: s.start,
    )
    bounds: list[tuple[int, float]] = [(0, float("-inf"))]
    for s in restarts:
        try:
            inc = int(s.fields.get("incarnation", bounds[-1][0] + 1))
        except (TypeError, ValueError):
            inc = bounds[-1][0] + 1
        if inc == bounds[-1][0]:
            continue  # another node's restart for the same incarnation
        bounds.append((inc, s.start))
    return bounds


def _bin_incarnation(bounds: list[tuple[int, float]], t: float) -> int:
    inc = bounds[0][0]
    for b_inc, b_start in bounds:
        if t >= b_start:
            inc = b_inc
        else:
            break
    return inc


def _efficiency_rows(spans: list[Span]) -> list[dict]:
    """Steady-state efficiency per incarnation from the trainer's
    journaled ``metrics_sample`` points (telemetry/efficiency.py) and
    the phases every ``train_step`` point carries as ``<phase>_s``
    fields (trainer/elastic_trainer.py): MFU summary, mean step time, per-phase
    seconds and share of step, and the %-of-samples host-blocked — the
    table that answers "where does a healthy step go" beside the
    lost-time table's "where did the failures' time go"."""
    bounds = _incarnation_bounds(spans)
    per_inc: dict[int, dict] = {}

    def bucket(inc: int) -> dict:
        return per_inc.setdefault(inc, {
            "mfu": [], "step_s": [], "blocked": [], "phases": {},
        })

    for span in spans:
        if span.name == "metrics_sample":
            b = bucket(_bin_incarnation(bounds, span.end))
            mfu = span.fields.get("mfu")
            if isinstance(mfu, (int, float)):
                b["mfu"].append(float(mfu))
            step_s = span.fields.get("step_s")
            if isinstance(step_s, (int, float)):
                b["step_s"].append(float(step_s))
            frac = span.fields.get("host_blocked_frac")
            if isinstance(frac, (int, float)):
                b["blocked"].append(float(frac))
        elif span.name == "train_step":
            b = bucket(_bin_incarnation(bounds, span.end))
            for key, value in span.fields.items():
                if key.endswith("_s") and isinstance(value, (int, float)):
                    b["phases"].setdefault(key[:-2], []).append(
                        max(0.0, float(value)))

    def mean(xs: list[float]) -> float | None:
        return sum(xs) / len(xs) if xs else None

    rows: list[dict] = []
    for inc in sorted(per_inc):
        b = per_inc[inc]
        if not (b["step_s"] or b["mfu"] or b["phases"]):
            continue
        phase_s = {p: mean(v) for p, v in sorted(b["phases"].items())}
        step_mean = mean(b["step_s"])
        denom = step_mean or sum(v for v in phase_s.values() if v) or 0.0
        counts = [len(b["step_s"]), len(b["mfu"])]
        counts += [len(v) for v in b["phases"].values()]
        row = {
            "incarnation": inc,
            "samples": max(counts),
            "mfu_mean": round(mean(b["mfu"]), 4) if b["mfu"] else None,
            "mfu_min": round(min(b["mfu"]), 4) if b["mfu"] else None,
            "mfu_max": round(max(b["mfu"]), 4) if b["mfu"] else None,
            "step_s_mean": round(step_mean, 6) if step_mean else None,
            "host_blocked_pct": (
                round(100.0 * mean(b["blocked"]), 1)
                if b["blocked"] else None
            ),
            "phase_s": {p: round(v, 6) for p, v in phase_s.items()
                        if v is not None},
            "phase_pct": {
                p: round(100.0 * v / denom, 1)
                for p, v in phase_s.items()
                if v is not None and denom > 0
            },
        }
        rows.append(row)
    return rows


def _master_saturation_rows(spans: list[Span]) -> list[dict]:
    """Control-plane saturation per node-count tier (DESIGN.md §22).

    ``master_rpc`` journal points — one per cost center, emitted by a
    real master at stop and by each fleet-simulator run — are grouped
    by their ``nodes`` tier; within a tier the center with the largest
    total handler time is named dominant. Repeated emissions for the
    same (tier, center) keep the last one (cumulative counters: the
    final emission supersedes earlier ones).
    """
    tiers: dict[int, dict[str, dict]] = {}
    for span in spans:
        if span.name != "master_rpc":
            continue
        center = str(span.fields.get("rpc", "") or "")
        if not center:
            continue
        try:
            tier = int(span.fields.get("nodes", 0) or 0)
            row = {
                "rpc": center,
                "calls": int(span.fields.get("calls", 0) or 0),
                "total_ms": float(span.fields.get("total_ms", 0.0)
                                  or 0.0),
                "p99_ms": float(span.fields.get("p99_ms", 0.0) or 0.0),
            }
        except (TypeError, ValueError):
            continue
        tiers.setdefault(tier, {})[center] = row
    out: list[dict] = []
    for tier in sorted(tiers):
        rows = sorted(tiers[tier].values(),
                      key=lambda r: (-r["total_ms"], r["rpc"]))
        out.append({
            "nodes": tier,
            "dominant": rows[0]["rpc"],
            "dominant_total_ms": rows[0]["total_ms"],
            "total_ms": {r["rpc"]: r["total_ms"] for r in rows},
            "rpc_p99_ms": {r["rpc"]: r["p99_ms"] for r in rows},
            "calls": {r["rpc"]: r["calls"] for r in rows},
        })
    return out


def _serving_observatory_rows(spans: list[Span]) -> list[dict]:
    """Serving memory observatory per engine process (DESIGN.md §29).

    ``kv_pool`` journal points — periodic samples from
    ``serving/observatory.py`` — are grouped by emitting process.
    Occupancy summarizes over the sample series (mean + p95: how hard
    the page pool ran); shareable fraction and the COW multiplier
    report their maxima (the best dedup opportunity observed); the
    acceptance numbers come from the LAST sample, whose counters are
    cumulative over the engine's lifetime.
    """
    per_proc: dict[str, list[Span]] = {}
    for span in spans:
        if span.name == "kv_pool":
            per_proc.setdefault(span.proc or "unknown", []).append(span)
    rows: list[dict] = []
    for proc in sorted(per_proc):
        samples = sorted(per_proc[proc], key=lambda s: s.end)
        occ = sorted(
            float(s.fields.get("occupancy", 0.0) or 0.0)
            for s in samples
        )
        last = samples[-1].fields

        def fmax(key: str) -> float:
            return max(
                float(s.fields.get(key, 0.0) or 0.0) for s in samples
            )

        rows.append({
            "proc": proc,
            "samples": len(samples),
            "kv_pages_total": int(last.get("total", 0) or 0),
            "kv_occupancy_mean": round(sum(occ) / len(occ), 4),
            "kv_occupancy_p95": round(
                occ[min(len(occ) - 1, int(0.95 * len(occ)))], 4),
            "kv_pages_high_water": int(fmax("high_water")),
            "pages_shareable_frac": round(fmax("shareable_frac"), 4),
            "cow_multiplier": round(fmax("cow_multiplier"), 4),
            "largest_family": int(fmax("largest_family")),
            "draft_accept_rate": round(
                float(last.get("accept_rate", 0.0) or 0.0), 4),
            "tokens_scored": int(last.get("scored", 0) or 0),
            "accept_run_p50": int(last.get("accept_run_p50", 0) or 0),
            "accept_run_p95": int(last.get("accept_run_p95", 0) or 0),
        })
    return rows


def _per_incarnation(spans: list[Span],
                     window: tuple[float, float] | None,
                     median: float,
                     goodput_log: str | None) -> list[dict]:
    """Attribute each phase to the incarnation it recovered INTO.

    Incarnation windows come from ``node_restart`` spans (each carries
    the incarnation it is bringing up); spans are binned by start time,
    so one slow rendezvous or restore is pinned to the incarnation that
    suffered it rather than averaged over the job.
    """
    bounds = _incarnation_bounds(spans)
    per_inc: dict[int, dict[str, list[tuple[float, float]]]] = {}
    for span in spans:
        cat = CATEGORY_OF.get(span.name)
        if cat is None:
            continue
        inc = _bin_incarnation(bounds, span.start)
        start, end = span.start, span.end
        if span.name == "compile" and median > 0:
            end = max(start, end - median)
        per_inc.setdefault(inc, {}).setdefault(cat, []).append((start, end))
        if cat == "recompile":
            per_inc.setdefault(inc, {}).setdefault(
                _recompile_sub(span), []).append((start, end))
    redone = _redone_by_incarnation(goodput_log) if goodput_log else {}
    rows = []
    for inc in sorted(set(per_inc) | set(redone)):
        row: dict = {"incarnation": inc}
        for cat in CATEGORIES + RECOMPILE_SUBCATEGORIES:
            if cat == "redone":
                continue
            row[f"{cat}_s"] = round(_union_seconds(
                per_inc.get(inc, {}).get(cat, ()), window
            ), 4)
        row["redone_steps"] = redone.get(inc, 0)
        row["redone_s"] = round(redone.get(inc, 0) * median, 4)
        rows.append(row)
    return rows


def format_report(report: LostTimeReport) -> str:
    lines = [
        f"lost-time breakdown ({report.n_spans} spans, "
        f"traces: {', '.join(report.traces) or 'none'})",
        f"  total wall (warm) : {report.total_s:10.2f} s",
        f"  productive        : {report.productive_s:10.2f} s"
        f"   (goodput {report.goodput:.4f})",
        f"  lost              : {report.lost_s:10.2f} s",
    ]
    for cat in CATEGORIES:
        lines.append(
            f"    {cat:<14}  : {report.categories.get(cat, 0.0):10.2f} s"
        )
        if cat == "recompile":
            for sub in RECOMPILE_SUBCATEGORIES:
                label = sub.replace("recompile_", "· ")
                lines.append(
                    f"      {label:<12}  : "
                    f"{report.categories.get(sub, 0.0):10.2f} s"
                )
    lines.append(f"    {'unattributed':<14}  : "
                 f"{report.unattributed_s:10.2f} s")
    if report.incarnations:
        lines.append("  per incarnation (same phase names as bench):")
        lines.append("    inc   respawn  rendezvous   restore  recompile"
                     "    redone")
        for row in report.incarnations:
            lines.append(
                f"    {row['incarnation']:>3}"
                f"  {row.get('respawn_s', 0.0):8.2f}"
                f"  {row.get('rendezvous_s', 0.0):10.2f}"
                f"  {row.get('restore_s', 0.0):8.2f}"
                f"  {row.get('recompile_s', 0.0):9.2f}"
                f"  {row.get('redone_s', 0.0):8.2f}"
            )
    if report.efficiency:
        lines.append("  steady-state efficiency (journaled samples, "
                     "telemetry/efficiency.py):")
        lines.append("    inc       mfu    step_s  %host-blocked"
                     "  phase breakdown (% of step)")
        def cell(v, width: int, fmt: str) -> str:
            return f"{v:{width}{fmt}}" if v is not None else f"{'n/a':>{width}}"

        for row in report.efficiency:
            phases = ", ".join(
                f"{p}={v:.0f}%" for p, v in
                sorted(row.get("phase_pct", {}).items(),
                       key=lambda kv: -kv[1])
            )
            lines.append(
                f"    {row['incarnation']:>3}"
                f"  {cell(row.get('mfu_mean'), 8, '.4f')}"
                f"  {cell(row.get('step_s_mean'), 8, '.4f')}"
                f"  {cell(row.get('host_blocked_pct'), 13, '.1f')}"
                f"  {phases}"
            )
    if report.master_saturation:
        lines.append("  master saturation (control-plane cost centers "
                     "per node tier, DESIGN.md §22):")
        for tier in report.master_saturation:
            lines.append(
                f"    {tier['nodes']:>6} nodes  dominant: "
                f"{tier['dominant']} "
                f"({tier['dominant_total_ms']:.1f} ms total)"
            )
            top = sorted(tier["total_ms"].items(),
                         key=lambda kv: -kv[1])[:5]
            for center, total_ms in top:
                p99 = tier["rpc_p99_ms"].get(center, 0.0)
                calls = tier["calls"].get(center, 0)
                lines.append(
                    f"      {center:<28} {total_ms:10.1f} ms"
                    f"  p99 {p99:8.3f} ms  x{calls}"
                )
    if report.serving_observatory:
        lines.append("  serving memory observatory (kv_pool samples, "
                     "DESIGN.md §29):")
        lines.append("    proc              occ-mean  occ-p95  hi-water"
                     "  share-frac  cow-mult  accept  run-p50/p95")
        for row in report.serving_observatory:
            lines.append(
                f"    {row['proc']:<16}"
                f"  {row['kv_occupancy_mean']:8.4f}"
                f"  {row['kv_occupancy_p95']:7.4f}"
                f"  {row['kv_pages_high_water']:8d}"
                f"  {row['pages_shareable_frac']:10.4f}"
                f"  {row['cow_multiplier']:8.4f}"
                f"  {row['draft_accept_rate']:6.4f}"
                f"  {row['accept_run_p50']}/{row['accept_run_p95']}"
            )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        "python -m dlrover_tpu.telemetry.report",
        description="attribute lost training time by cause",
    )
    parser.add_argument("--journal", required=True,
                        help="journal file or DLROVER_TPU_JOURNAL_DIR dir")
    parser.add_argument("--goodput-log", default="",
                        help="per-step goodput JSONL (utils/goodput.py); "
                             "anchors total lost time when given")
    parser.add_argument("--end-time", type=float, default=None)
    parser.add_argument("--trace", default=None,
                        help="restrict to one trace id")
    parser.add_argument("--format", choices=("text", "json"),
                        default="text",
                        help="json: one document with stable keys "
                             "(CI/bench consumption)")
    parser.add_argument("--json", action="store_true",
                        help="alias for --format json")
    args = parser.parse_args(argv)
    report = build_report(
        args.journal, goodput_log=args.goodput_log or None,
        end_time=args.end_time, trace=args.trace,
    )
    if args.json or args.format == "json":
        print(json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(format_report(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
