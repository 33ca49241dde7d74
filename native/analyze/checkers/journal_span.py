"""Rule ``journal-span``: journal emissions use literal, documented
names, and every ``begin`` has a matching ``end``.

Span names are the contract ``telemetry/report.py`` attributes lost
time by and ``telemetry/timeline.py`` renders lanes from; an
undocumented or dynamic name is a span the operator cannot read, and a
``begin`` with no ``end`` renders every run as "process died inside
the span" even when it didn't. Subsumes (as AST, not regex) the span
half of the original ``native/check_metric_names.py`` lint and adds
the open/close pairing the regex could never see:

- ``.emit("name")`` / ``.begin("name")`` / ``.span("name")`` first
  arguments, and those of the hot-path helpers ``hot_span("name")`` /
  ``annotate("name")`` (``telemetry/journal.py``: a journal span paired
  with a profiler annotation), must be string literals matching
  ``[a-z_][a-z0-9_]*`` and appear verbatim in DESIGN.md;
- a ``sid = X.begin("name")`` must be paired, within the same function
  or (via a ``self.attr``) the same class, with an ``X.end(sid, ...)``
  — the ``span()`` context manager pairs itself and is always fine;
- a ``remote_parent=`` argument must be an expression (an envelope /
  payload / spawn-env field), never a string literal: a literal
  context would hard-wire fake causality into the trace fabric
  (DESIGN.md §27).

``telemetry/journal.py`` is excluded: it implements the API and
forwards caller-supplied names.
"""

from __future__ import annotations

import ast
import re

from native.analyze.core import (
    Checker,
    Finding,
    Module,
    Project,
    literal_str,
    register,
)

# (a digit inside a name is fine: the step phase `h2d`)
SPAN_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
EXCLUDE_SUFFIXES = ("telemetry/journal.py",)
SPAN_METHODS = ("emit", "begin", "span")
# the hot-path helpers that pair a journal span with a profiler
# annotation (telemetry/journal.py): plain functions, same contract
SPAN_FUNCTIONS = ("hot_span", "annotate")


def _first_arg(call: ast.Call) -> ast.AST | None:
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "name":
            return kw.value
    return None


@register
class JournalSpanChecker(Checker):
    rule = "journal-span"
    description = ("journal span names are literal [a-z_][a-z0-9_]* documented "
                   "in DESIGN.md; every .begin() is paired with .end() "
                   "in the same function or class")
    hint = ('use `with journal.span("name"):` (self-pairing), or keep '
            "the begin's span id and call `journal.end(sid, \"name\", "
            "start=t0)` on every exit path; document the name in the "
            "DESIGN.md span table")

    def check(self, project: Project) -> list[Finding]:
        findings: list[Finding] = []
        for module in project.modules:
            if module.relpath.endswith(EXCLUDE_SUFFIXES):
                continue
            findings.extend(self._check_names(module, project))
            findings.extend(self._check_pairing(module))
        return findings

    # ----------------------------------------------------------- span names

    def _check_names(self, module: Module,
                     project: Project) -> list[Finding]:
        findings: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in SPAN_METHODS:
                how = f".{node.func.attr}()"
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in SPAN_FUNCTIONS:
                how = f"{node.func.id}()"
            else:
                continue
            arg = _first_arg(node)
            if arg is None:
                continue
            name = literal_str(arg)
            if name is None:
                # non-literal: f-strings/vars defeat grep and the
                # DESIGN.md contract
                findings.append(self.finding(
                    module, node,
                    f"journal {how} with a non-literal "
                    "span name — names must be grep-able literals",
                ))
                continue
            if not SPAN_NAME_RE.match(name):
                findings.append(self.finding(
                    module, node,
                    f"span name {name!r} does not match "
                    f"{SPAN_NAME_RE.pattern}",
                ))
                continue
            if name not in project.design_text:
                findings.append(self.finding(
                    module, node,
                    f"journal span {name!r} is not documented in "
                    "DESIGN.md; add it to the span-name table",
                ))
            for kw in node.keywords:
                if kw.arg == "remote_parent" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value:
                    # a literal remote_parent fabricates causality: the
                    # context must arrive through an RPC envelope,
                    # message payload field, or the spawn environment
                    findings.append(self.finding(
                        module, node,
                        f"journal span {name!r} passes a literal "
                        "remote_parent — the context string must come "
                        "from an envelope/payload/spawn-env field "
                        "(§27), never be hard-wired",
                    ))
        return findings

    # -------------------------------------------------------- begin pairing

    def _check_pairing(self, module: Module) -> list[Finding]:
        findings: list[Finding] = []
        # class-level pass: begin stored to self.attr may be ended in a
        # sibling method
        for class_node in module.classes():
            ended_attrs = self._ended_self_attrs(class_node)
            for item in class_node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    findings.extend(self._check_function(
                        module, item, ended_attrs))
        # module-level functions
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(self._check_function(module, node, set()))
        return findings

    def _ended_self_attrs(self, class_node: ast.ClassDef) -> set[str]:
        """self attributes passed as first arg to any .end() call in the
        class."""
        ended: set[str] = set()
        for node in ast.walk(class_node):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "end" and node.args:
                first = node.args[0]
                if isinstance(first, ast.Attribute) \
                        and isinstance(first.value, ast.Name) \
                        and first.value.id == "self":
                    ended.add(first.attr)
        return ended

    def _check_function(self, module: Module, func: ast.FunctionDef,
                        class_ended: set[str]) -> list[Finding]:
        begins: list[tuple[str | None, str | None, ast.Call]] = []
        ended_names: set[str] = set()
        for node in ast.walk(func):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            if node.func.attr == "end" and node.args:
                first = node.args[0]
                if isinstance(first, ast.Name):
                    ended_names.add(first.id)
                elif isinstance(first, ast.Attribute) \
                        and isinstance(first.value, ast.Name) \
                        and first.value.id == "self":
                    ended_names.add(f"self.{first.attr}")
        # find begin assignments and bare begins
        for node in ast.walk(func):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr == "begin":
                target = node.targets[0]
                if isinstance(target, ast.Name):
                    begins.append((target.id, None, node.value))
                elif isinstance(target, ast.Attribute) \
                        and isinstance(target.value, ast.Name) \
                        and target.value.id == "self":
                    begins.append((None, target.attr, node.value))
                else:
                    begins.append((None, None, node.value))
            elif isinstance(node, ast.Expr) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr == "begin":
                # begin whose span id is dropped can never be ended
                begins.append((None, None, node.value))
        findings: list[Finding] = []
        for var, attr, call in begins:
            if var is not None and var in ended_names:
                continue
            if attr is not None and (attr in class_ended
                                     or f"self.{attr}" in ended_names):
                continue
            name = literal_str(_first_arg(call) or ast.Constant(value=""))
            findings.append(self.finding(
                module, call,
                f"journal .begin({(name or '<dynamic>')!r}) has no "
                "matching .end() in the same function/class — the span "
                "reads as 'process died inside' on every run",
            ))
        return findings
