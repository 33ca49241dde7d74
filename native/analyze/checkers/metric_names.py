"""Rule ``metric-name``: metric + chaos-fault-point naming contracts.

This is the ported PR-1/PR-4 lint (`native/check_metric_names.py`, now
a shim over this module): every ``registry().counter/gauge/histogram``
registration uses a literal ``dlrover_tpu_[a-z_]+`` name, names are
registered at exactly one call site, contract-family names and labels
appear verbatim in DESIGN.md, and ``chaos.fire`` injection points are
literal, well-formed and documented. Journal spans moved to the
dedicated ``journal-span`` rule (AST-based, adds open/close pairing);
the legacy ``scan_spans`` function is kept here because the shim and
the telemetry tests call it directly.

The scanning stays regex-based on purpose — it predates the framework,
its behavior is pinned by tier-1 tests, and the name/site extraction
has no need for dataflow. The checker class adapts its problem strings
into framework findings.
"""

from __future__ import annotations

import os
import re
import sys

from native.analyze.core import Checker, Finding, Project, register

NAME_RE = re.compile(r"^dlrover_tpu_[a-z_]+$")
REG_RE = re.compile(
    r"\.\s*(counter|gauge|histogram)\(\s*(?:\n\s*)?"
    r"(?:(?P<q>['\"])(?P<name>[^'\"]+)(?P=q)|(?P<nonlit>[A-Za-z_f][^,)]*))"
)
# (a digit inside a name is fine: the step phase `h2d`)
SPAN_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
# journal methods, and the hot-path helpers that pair a journal span
# with a profiler annotation (telemetry/journal.py hot_span / annotate)
SPAN_RE = re.compile(
    r"(?:\.\s*(?:emit|begin|span)|\b(?:hot_span|annotate))\(\s*(?:\n\s*)?"
    r"(?:(?P<q>['\"])(?P<name>[^'\"]+)(?P=q)|(?P<nonlit>[A-Za-z_f][^,)]*))"
)
# the journal implementation itself forwards caller-supplied names
# (EventJournal.span -> self.begin(name, ...)): not an emission site
SPAN_SCAN_EXCLUDE = (os.path.join("telemetry", "journal.py"),)

POINT_NAME_RE = re.compile(r"^[a-z_]+$")
POINT_RE = re.compile(
    r"chaos\s*\.\s*fire\(\s*(?:\n\s*)?"
    r"(?:(?P<q>['\"])(?P<name>[^'\"]+)(?P=q)|(?P<nonlit>[A-Za-z_f][^,)]*))"
)
# the chaos package itself forwards caller-supplied point names and its
# docstrings discuss the call form: not injection sites
POINT_SCAN_EXCLUDE = (os.path.join("dlrover_tpu", "chaos") + os.sep,)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
PKG = os.path.join(_REPO, "dlrover_tpu")
DESIGN_MD = os.path.join(_REPO, "DESIGN.md")
# metric families whose names are an operator contract: every
# registered name under these prefixes must appear verbatim in DESIGN.md
DOCUMENTED_PREFIXES = (
    "dlrover_tpu_gateway_",
    "dlrover_tpu_standby_",
    "dlrover_tpu_snapshot_interval_",
    # elastic resharding + compile cache (DESIGN.md §17): the runbook
    # "failover is recompiling" keys on these names
    "dlrover_tpu_compile_cache_",
    "dlrover_tpu_reshard_",
    # efficiency observatory (DESIGN.md §18): the "MFU dropped" runbook
    # keys on the live MFU gauge, the step-phase histogram, and the
    # profiler-capture counters
    "dlrover_tpu_mfu",
    "dlrover_tpu_step_phase_",
    "dlrover_tpu_profile_",
    # parallel persist / verified restore (DESIGN.md §20): the "restore
    # after shrinking the job" runbook keys on the ckpt family
    "dlrover_tpu_ckpt_",
    # MPMD pipeline runtime (DESIGN.md §21): the "one pipeline stage is
    # slow / recompiling" runbook keys on the per-stage families
    "dlrover_tpu_pipeline_",
    # control-plane observatory (DESIGN.md §22): the "master is slow"
    # runbook keys on the dispatch/lock/ingest attribution families
    "dlrover_tpu_master_",
    # disaggregated serving data plane (DESIGN.md §23): the "TTFT is
    # spiking" runbook keys on the decode-stall histogram and the
    # paged-KV park/handoff counters
    "dlrover_tpu_engine_",
    # strategy autopilot (DESIGN.md §24): the "autopilot picked a bad
    # plan" runbook keys on the plan/retune counters and the
    # contradiction gauges
    "dlrover_tpu_autopilot_",
    # elastic embedding fabric (DESIGN.md §25): the "embedding
    # staleness is climbing" runbook keys on the staleness gauge and
    # the backpressure/apply-lag families
    "dlrover_tpu_embedding_",
    # master crash-failover (DESIGN.md §26): the "the master died"
    # runbook keys on the degraded/unreachable/reconcile/redelivery
    # families and the epoch gauge
    "dlrover_tpu_agent_",
    # causal trace fabric (DESIGN.md §27): the "where did this
    # request's / incident's time go" runbook keys on the span-write
    # and head-sampling-drop counters
    "dlrover_tpu_trace_",
    # rack sub-master tier (DESIGN.md §28): the "scaling past 1k
    # nodes" runbook keys on the merge/epoch/cache-lookup families
    # and the comm-world diff byte counters
    "dlrover_tpu_submaster_",
    # serving memory observatory (DESIGN.md §29): the "is the KV pool
    # the bottleneck" runbook keys on the request-latency family and
    # the engine kv_/draft_ gauges (covered by the engine_ prefix)
    "dlrover_tpu_serving_",
    # partition tolerance (DESIGN.md §30): the "a rack is partitioned
    # from the root" runbook keys on the link-transition/drop counters
    # and the lease-expiry / push-fence families
    "dlrover_tpu_partition_",
    # serving raw speed (DESIGN.md §31): the "acceptance collapsed"
    # runbook keys on the speculative-decode verify/accept families
    # (the COW kv_cow_ gauges ride the engine_/gateway_ prefixes)
    "dlrover_tpu_spec_",
)

# label names that are themselves an operator contract (dashboards and
# runbooks filter on them): each must be used by a registration in the
# package AND appear verbatim in DESIGN.md
CONTRACT_LABELS = ("straggler_phase",)


def check_contract_labels(pkg_dir: str = PKG,
                          design_path: str = DESIGN_MD) -> list[str]:
    """Contract labels must exist in code and be documented."""
    problems: list[str] = []
    source = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fname in sorted(files):
            if fname.endswith(".py"):
                with open(os.path.join(root, fname),
                          encoding="utf-8") as f:
                    source.append(f.read())
    source_text = "\n".join(source)
    try:
        with open(design_path, encoding="utf-8") as f:
            design = f.read()
    except OSError as e:
        return [f"cannot read {design_path}: {e}"]
    for label in CONTRACT_LABELS:
        if f'"{label}"' not in source_text \
                and f"'{label}'" not in source_text:
            problems.append(
                f"contract label {label!r} is not used by any metric "
                "registration in the package"
            )
        if label not in design:
            problems.append(
                f"contract label {label!r} is not documented in "
                "DESIGN.md; add it to its metrics table"
            )
    return problems


def check_documented(names: dict[str, list[str]],
                     design_path: str = DESIGN_MD) -> list[str]:
    """Every contract-family metric registered in code must appear in
    DESIGN.md (gateway, warm-standby, interval tuner)."""
    try:
        with open(design_path, encoding="utf-8") as f:
            design = f.read()
    except OSError as e:
        return [f"cannot read {design_path}: {e}"]
    return [
        f"metric {name!r} ({', '.join(sites)}) is not documented in "
        f"DESIGN.md; add it to its metrics table"
        for name, sites in sorted(names.items())
        if any(name.startswith(p) for p in DOCUMENTED_PREFIXES)
        and name not in design
    ]


def scan_spans(pkg_dir: str = PKG,
               design_path: str = DESIGN_MD) -> tuple[dict[str, list[str]],
                                                      list[str]]:
    """(span name -> [emission sites], problems) for journal spans.

    Legacy entry point kept for the shim and the telemetry tests; the
    framework's ``journal-span`` rule supersedes it (AST walk + begin/
    end pairing) but asserts the same naming/documentation contract.
    """
    names: dict[str, list[str]] = {}
    problems: list[str] = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, os.path.dirname(pkg_dir))
            if rel.endswith(SPAN_SCAN_EXCLUDE):
                continue
            with open(path, encoding="utf-8") as f:
                text = f.read()
            for match in SPAN_RE.finditer(text):
                line = text.count("\n", 0, match.start()) + 1
                site = f"{rel}:{line}"
                if match.group("name") is None:
                    problems.append(
                        f"{site}: journal span emitted with a non-literal "
                        f"name ({match.group('nonlit')!r})"
                    )
                    continue
                name = match.group("name")
                if not SPAN_NAME_RE.match(name):
                    problems.append(
                        f"{site}: span name {name!r} does not match "
                        f"{SPAN_NAME_RE.pattern}"
                    )
                names.setdefault(name, []).append(site)
    try:
        with open(design_path, encoding="utf-8") as f:
            design = f.read()
    except OSError as e:
        problems.append(f"cannot read {design_path}: {e}")
        return names, problems
    for name, sites in sorted(names.items()):
        if name not in design:
            problems.append(
                f"journal span {name!r} ({', '.join(sites)}) is not "
                f"documented in DESIGN.md; add it to the span-name table"
            )
    return names, problems


def scan_fault_points(pkg_dir: str = PKG,
                      design_path: str = DESIGN_MD
                      ) -> tuple[dict[str, list[str]], list[str]]:
    """(fault point name -> [injection sites], problems) for the chaos
    harness's ``chaos.fire("...")`` call sites."""
    names: dict[str, list[str]] = {}
    problems: list[str] = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, os.path.dirname(pkg_dir))
            if any(ex in rel for ex in POINT_SCAN_EXCLUDE):
                continue
            with open(path, encoding="utf-8") as f:
                text = f.read()
            for match in POINT_RE.finditer(text):
                line = text.count("\n", 0, match.start()) + 1
                site = f"{rel}:{line}"
                if match.group("name") is None:
                    problems.append(
                        f"{site}: chaos fault point fired with a "
                        f"non-literal name ({match.group('nonlit')!r})"
                    )
                    continue
                name = match.group("name")
                if not POINT_NAME_RE.match(name):
                    problems.append(
                        f"{site}: fault point name {name!r} does not "
                        f"match {POINT_NAME_RE.pattern}"
                    )
                names.setdefault(name, []).append(site)
    try:
        with open(design_path, encoding="utf-8") as f:
            design = f.read()
    except OSError as e:
        problems.append(f"cannot read {design_path}: {e}")
        return names, problems
    for name, sites in sorted(names.items()):
        if name not in design:
            problems.append(
                f"chaos fault point {name!r} ({', '.join(sites)}) is not "
                f"documented in DESIGN.md; add it to the fault-point table"
            )
    return names, problems


def scan(pkg_dir: str = PKG,
         design_path: str = DESIGN_MD
         ) -> tuple[dict[str, list[str]], list[str]]:
    """(name -> [call sites], problems)."""
    names: dict[str, list[str]] = {}
    problems: list[str] = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, os.path.dirname(pkg_dir))
            with open(path, encoding="utf-8") as f:
                text = f.read()
            for match in REG_RE.finditer(text):
                line = text.count("\n", 0, match.start()) + 1
                site = f"{rel}:{line}"
                if match.group("name") is None:
                    # non-literal first argument: the lint (and grep-
                    # ability) relies on literal names at the call site
                    problems.append(
                        f"{site}: metric registered with a non-literal "
                        f"name ({match.group('nonlit')!r})"
                    )
                    continue
                name = match.group("name")
                if not NAME_RE.match(name):
                    problems.append(
                        f"{site}: metric name {name!r} does not match "
                        f"{NAME_RE.pattern}"
                    )
                names.setdefault(name, []).append(site)
    for name, sites in sorted(names.items()):
        if len(sites) > 1:
            problems.append(
                f"metric {name!r} registered at {len(sites)} call sites "
                f"({', '.join(sites)}); names must be unique"
            )
    problems.extend(check_documented(names, design_path=design_path))
    return names, problems


_SITE_RE = re.compile(r"^(?P<path>[^:\s]+):(?P<line>\d+): (?P<msg>.*)$",
                      re.DOTALL)


def _problem_to_finding(rule: str, problem: str, hint: str,
                        fallback_path: str) -> Finding:
    """Adapt a legacy 'rel:line: msg' problem string into a Finding.

    The line is carried separately and stripped from the message so the
    baseline key stays stable when code above the site moves.
    """
    match = _SITE_RE.match(problem)
    if match:
        return Finding(rule=rule, path=match.group("path"),
                       line=int(match.group("line")),
                       message=match.group("msg"), hint=hint)
    return Finding(rule=rule, path=fallback_path, line=1,
                   message=problem, hint=hint)


@register
class MetricNamesChecker(Checker):
    rule = "metric-name"
    description = ("metric registrations use unique literal "
                   "dlrover_tpu_[a-z_]+ names; contract families, "
                   "labels and chaos fault points documented in "
                   "DESIGN.md")
    hint = ('registry().counter("dlrover_tpu_<subsystem>_<what>", ...) '
            "with a string literal; add contract-family names to their "
            "DESIGN.md metrics table")

    def check(self, project: Project) -> list[Finding]:
        findings: list[Finding] = []
        pkg = project.package_dir
        design = project.design_path
        _, problems = scan(pkg, design_path=design)
        for p in problems:
            findings.append(_problem_to_finding(
                self.rule, p, self.hint, project.package))
        _, point_problems = scan_fault_points(pkg, design_path=design)
        for p in point_problems:
            findings.append(_problem_to_finding(
                self.rule, p,
                'chaos.fire("<point_name>") with a literal [a-z_]+ name '
                "documented in the DESIGN.md fault-point table",
                project.package))
        for p in check_contract_labels(pkg, design_path=design):
            findings.append(_problem_to_finding(
                self.rule, p, self.hint, project.package))
        return findings


def main() -> int:
    names, problems = scan()
    span_names, span_problems = scan_spans()
    point_names, point_problems = scan_fault_points()
    problems = (problems + span_problems + point_problems
                + check_contract_labels())
    if problems:
        for p in problems:
            print(f"check_metric_names: {p}", file=sys.stderr)
        return 1
    print(f"check_metric_names: {len(names)} metric names, "
          f"{len(span_names)} span names, "
          f"{len(point_names)} chaos fault points OK")
    return 0
