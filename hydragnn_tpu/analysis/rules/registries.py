"""Cross-artifact registry rules: env knobs, health kinds, config keys.

The drift these catch accumulated over eight PRs: 80+ ``HYDRAGNN_*``
knobs spread across five config layers with no single inventory, health
event kinds added in code but never documented (or documented and then
renamed), and finalize-written config keys nobody validates on read.
The registries (`analysis/registry.py`) are the declared truth; these
rules pin code and docs to them from both directions.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..astutil import const_str
from ..core import Finding, Rule, Severity, register
from ..registry import (
    HEALTH_KINDS,
    KERNEL_NAMES,
    KNOBS,
    SCOPE_NAMES,
    SPAN_NAMES,
    TRACE_DOC_BEGIN,
    TRACE_DOC_END,
    emit_knob_docs,
    emit_trace_docs,
)

_KNOB_RE = re.compile(r"HYDRAGNN_[A-Z0-9_]+")


def _knob_mentions(text: str) -> Set[str]:
    """Complete knob names in a string — a match ending in ``_`` is a
    prefix construction (``"HYDRAGNN_SERVE_" + name``), not a knob."""
    return {m for m in _KNOB_RE.findall(text) if not m.endswith("_")}


def _string_constants(tree: ast.AST):
    for node in ast.walk(tree):
        s = const_str(node)
        if s is not None:
            yield node, s
        elif isinstance(node, ast.JoinedStr):
            for v in node.values:
                sv = const_str(v)
                if sv is not None:
                    yield node, sv


@register
class UndeclaredEnvKnob(Rule):
    id = "REG001"
    name = "undeclared-env-knob"
    severity = Severity.ERROR
    doc = ("every HYDRAGNN_* name in code must be declared in the knob "
           "registry (analysis/registry.py)")

    def check_file(self, ctx) -> Iterable[Finding]:
        if ctx.rel.endswith("analysis/registry.py"):
            return []
        out: List[Finding] = []
        seen: Set[Tuple[int, str]] = set()
        for node, s in _string_constants(ctx.tree):
            for name in sorted(_knob_mentions(s)):
                if name in KNOBS:
                    continue
                key = (node.lineno, name)
                if key in seen:
                    continue
                seen.add(key)
                out.append(self.finding(
                    ctx, node,
                    f"env knob `{name}` is not declared in the knob "
                    f"registry (hydragnn_tpu/analysis/registry.py) — "
                    f"declare it (name/config/default/module/effect), "
                    f"then `tools/graftlint.py --emit-docs`"))
        return out


@register
class KnobRegistryDrift(Rule):
    id = "REG002"
    name = "knob-registry-drift"
    severity = Severity.WARN
    doc = ("every declared knob must still be read somewhere, and "
           "docs/KNOBS.md must match the generated registry table")

    def check_project(self, project) -> Iterable[Finding]:
        out: List[Finding] = []
        reg_ctx = next((f for f in project.files
                        if f.rel.endswith("analysis/registry.py")), None)

        def reg_line(name: str) -> int:
            if reg_ctx is None:
                return 1
            for i, line in enumerate(reg_ctx.lines, start=1):
                if f'"{name}"' in line:
                    return i
            return 1

        used: Set[str] = set()
        for f in project.files:
            # the registry's own declarations don't count as use — every
            # declared knob trivially appears there (REG001 excludes the
            # file for the same reason)
            if f.rel.endswith("analysis/registry.py"):
                continue
            used |= _knob_mentions(f.src)
        for name in sorted(KNOBS):
            if name not in used and reg_ctx is not None:
                out.append(self.finding(
                    reg_ctx, reg_line(name),
                    f"declared knob `{name}` is never mentioned in code "
                    f"— delete the registry entry (and its doc row) or "
                    f"wire the knob up"))

        docs = project.read_text("docs/KNOBS.md")
        if reg_ctx is not None and docs != emit_knob_docs():
            out.append(self.finding(
                reg_ctx, 1,
                "docs/KNOBS.md is missing or stale — regenerate with "
                "`python tools/graftlint.py --emit-docs`"))
        return out


def _health_kind_literals(call: ast.Call) -> Optional[List[str]]:
    """Kind literal(s) of a ``health(...)`` call: a string constant, or
    a conditional expression whose branches are both string constants.
    None = dynamic."""
    if not call.args:
        return None
    a = call.args[0]
    s = const_str(a)
    if s is not None:
        return [s]
    if isinstance(a, ast.IfExp):
        b, c = const_str(a.body), const_str(a.orelse)
        if b is not None and c is not None:
            return [b, c]
    return None


def _iter_health_calls(tree: ast.AST):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if name == "health" and node.args:
            yield node


@register
class UndeclaredHealthKind(Rule):
    id = "REG003"
    name = "undeclared-health-kind"
    severity = Severity.ERROR
    doc = ("every health(kind=...) literal must be declared in the "
           "health-kind registry; dynamic kinds need a suppression")

    def check_file(self, ctx) -> Iterable[Finding]:
        out: List[Finding] = []
        for call in _iter_health_calls(ctx.tree):
            kinds = _health_kind_literals(call)
            if kinds is None:
                out.append(self.finding(
                    ctx, call,
                    "health() called with a non-literal kind — the "
                    "registry rule cannot see it; pass literal kinds "
                    "(an IfExp of two literals is fine) or suppress "
                    "with a reason"))
                continue
            for kind in kinds:
                if kind not in HEALTH_KINDS:
                    out.append(self.finding(
                        ctx, call,
                        f"health kind `{kind}` is not declared in the "
                        f"health-kind registry (analysis/registry.py) — "
                        f"declare it and document it in "
                        f"docs/TELEMETRY.md"))
        return out


@register
class HealthKindDrift(Rule):
    id = "REG004"
    name = "health-kind-drift"
    severity = Severity.WARN
    doc = ("every declared health kind must be emitted somewhere in "
           "hydragnn_tpu/ and documented in docs/TELEMETRY.md")

    def check_project(self, project) -> Iterable[Finding]:
        out: List[Finding] = []
        reg_ctx = next((f for f in project.files
                        if f.rel.endswith("analysis/registry.py")), None)
        if reg_ctx is None:
            return []

        def reg_line(name: str) -> int:
            for i, line in enumerate(reg_ctx.lines, start=1):
                if f'_h("{name}"' in line:
                    return i
            return 1

        emitted: Set[str] = set()
        for f in project.files:
            if not f.rel.startswith("hydragnn_tpu/"):
                continue
            for call in _iter_health_calls(f.tree):
                emitted |= set(_health_kind_literals(call) or ())

        docs = project.read_text("docs/TELEMETRY.md") or ""
        for kind in sorted(HEALTH_KINDS):
            if kind not in emitted:
                out.append(self.finding(
                    reg_ctx, reg_line(kind),
                    f"declared health kind `{kind}` is never emitted — "
                    f"dead schema; delete it from the registry and "
                    f"docs/TELEMETRY.md"))
            if f"`{kind}`" not in docs:
                out.append(self.finding(
                    reg_ctx, reg_line(kind),
                    f"declared health kind `{kind}` is not documented "
                    f"in docs/TELEMETRY.md"))
        return out


# trace-API entry points whose first positional arg is a declared name,
# and the registry that declares it.  ``span`` is deliberately held to a
# literal-only check (re.Match.span(1) and other unrelated ``.span()``
# spellings must not trip the rule); ``record_interval`` and the two
# scope helpers are unambiguous and also fail on dynamic names the
# registry cannot see.
_NAME_CALLS = {
    "span": ("span", SPAN_NAMES),
    "record_interval": ("span", SPAN_NAMES),
    "comm_region": ("scope", SCOPE_NAMES),
    "phase": ("scope", SCOPE_NAMES),
}
_STRICT_NAMES = ("record_interval", "comm_region", "phase")
# utils/tracer regions: ``tr.start("x")`` under the module's usual
# aliases only — ``thread.start()`` and ``profiler.stop()`` are not
# regions — and literal-only, like ``span``
_TRACER_ALIASES = ("tr", "tracer")
_TRACER_CALLS = ("start", "stop", "timer", "profile")


def _iter_name_calls(tree: ast.AST):
    """(what, registry, strict, function name, call) per trace-API call
    that takes a declared name first."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if (isinstance(fn, ast.Attribute) and name in _TRACER_CALLS
                and isinstance(fn.value, ast.Name)
                and fn.value.id in _TRACER_ALIASES):
            yield "span", SPAN_NAMES, False, f"{fn.value.id}.{name}", node
        elif name in _NAME_CALLS:
            what, registry = _NAME_CALLS[name]
            yield what, registry, name in _STRICT_NAMES, name, node


def _kernel_name_nodes(tree: ast.AST):
    """(node, value) of every place a kernel gets its name: the ``name=``
    of a ``pallas_call`` (None when it is missing), a ``kernel_name=``
    keyword, the default of a ``kernel_name`` parameter."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            callee = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            kws = {k.arg: k.value for k in node.keywords}
            if callee == "pallas_call":
                yield node, kws.get("name")
            if "kernel_name" in kws:
                yield node, kws["kernel_name"]
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            pos = args.posonlyargs + args.args
            for a, d in zip(pos[len(pos) - len(args.defaults):],
                            args.defaults):
                if a.arg == "kernel_name":
                    yield d, d
            for a, d in zip(args.kwonlyargs, args.kw_defaults):
                if a.arg == "kernel_name" and d is not None:
                    yield d, d


def _fstring_pattern(node: ast.JoinedStr) -> "re.Pattern":
    return re.compile("".join(
        re.escape(const_str(v)) if const_str(v) is not None else ".+"
        for v in node.values) + r"\Z")


@register
class UndeclaredSpanName(Rule):
    id = "REG006"
    name = "undeclared-span-name"
    severity = Severity.ERROR
    doc = ("every literal name passed to the trace API must be declared "
           "in analysis/registry.py: spans and host regions (span/"
           "record_interval, tracer start/stop/timer/profile), device "
           "scopes (phase/comm_region), kernel names (pallas_call "
           "name=); docs/TELEMETRY.md's name tables are generated")

    def check_file(self, ctx) -> Iterable[Finding]:
        out: List[Finding] = []
        for what, registry, strict, fname, call in _iter_name_calls(
                ctx.tree):
            s = const_str(call.args[0])
            if s is None:
                if strict:
                    out.append(self.finding(
                        ctx, call,
                        f"{fname}() called with a non-literal {what} "
                        f"name — the registry rule cannot see it; pass a "
                        f"declared literal or suppress with a reason"))
                continue
            if s not in registry:
                out.append(self.finding(
                    ctx, call,
                    f"{what} name `{s}` is not declared in the {what}-"
                    f"name registry (hydragnn_tpu/analysis/registry.py) "
                    f"— declare it (name/module/desc) and regenerate "
                    f"docs/TELEMETRY.md (`tools/graftlint.py "
                    f"--emit-docs`)"))
        for node, value in _kernel_name_nodes(ctx.tree):
            s = const_str(value) if value is not None else None
            if value is None:
                msg = ("pallas_call() without name= — the kernel would "
                       "be named by its caller's scope in a device trace")
            elif s is not None:
                msg = None if s in KERNEL_NAMES else (
                    f"kernel name `{s}` is not declared in KERNEL_NAMES "
                    f"(hydragnn_tpu/analysis/registry.py)")
            elif isinstance(value, ast.JoinedStr):
                pat = _fstring_pattern(value)
                msg = None if any(pat.match(k) for k in KERNEL_NAMES) \
                    else ("no declared kernel name has the form of this "
                          "f-string (KERNEL_NAMES, analysis/registry.py)")
            elif isinstance(value, ast.Name) and value.id == "kernel_name":
                msg = None  # the parameter: checked where it is given
            else:
                msg = ("kernel name the registry rule cannot see; pass "
                       "a declared literal or a `kernel_name` parameter")
            if msg:
                out.append(self.finding(ctx, node, msg))
        return out

    def check_project(self, project) -> Iterable[Finding]:
        reg_ctx = next((f for f in project.files
                        if f.rel.endswith("analysis/registry.py")), None)
        docs = project.read_text("docs/TELEMETRY.md") or ""
        a, b = docs.find(TRACE_DOC_BEGIN), docs.find(TRACE_DOC_END)
        if reg_ctx is not None and (
                a < 0 or b < 0
                or docs[a:b + len(TRACE_DOC_END)] + "\n"
                != emit_trace_docs()):
            return [self.finding(
                reg_ctx, 1,
                "docs/TELEMETRY.md's generated name tables are missing "
                "or stale — regenerate with `python tools/graftlint.py "
                "--emit-docs`")]
        return []


# (writer file, writer function, reader file, reader function) pairs for
# the finalize-written config sections.  Writers return a dict literal;
# readers consume keys via `<x>.get("key", ...)` — both key sets must
# match or a finalize-written key is never validated on read (or a read
# key silently has no written-back default).
CONFIG_KEY_SPECS = [
    ("hydragnn_tpu/serve/config.py", "serving_defaults",
     "hydragnn_tpu/serve/config.py", "from_section"),
    ("hydragnn_tpu/resilience/config.py", "resilience_training_defaults",
     "hydragnn_tpu/resilience/config.py", "from_training"),
    ("hydragnn_tpu/config/config.py", "_telemetry_defaults",
     "hydragnn_tpu/telemetry/logger.py", "from_section"),
]


def _function_def(tree: ast.AST, name: str) -> Optional[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == name:
            return node
    return None


def _dict_literal_keys(fn: ast.AST) -> Optional[Set[str]]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and isinstance(
                node.value, ast.Dict):
            keys = set()
            for k in node.value.keys:
                s = const_str(k)
                if s is None:
                    return None  # computed keys: not statically checkable
                keys.add(s)
            return keys
    return None


def _get_call_keys(fn: ast.AST) -> Set[str]:
    keys: Set[str] = set()
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get" and node.args):
            s = const_str(node.args[0])
            # env reads (`os.environ.get("HYDRAGNN_...")`) ride the same
            # .get spelling but are REG001/REG002's territory
            if s is not None and not s.startswith("HYDRAGNN_"):
                keys.add(s)
    return keys


@register
class ConfigKeyDrift(Rule):
    id = "REG005"
    name = "config-key-drift"
    severity = Severity.ERROR
    doc = ("finalize-written config defaults and their readers must "
           "agree key-for-key (every written key validated on read)")

    def check_project(self, project) -> Iterable[Finding]:
        out: List[Finding] = []
        specs = list(CONFIG_KEY_SPECS)
        # fixture support, EXPLICITLY scoped: only files named
        # `reg005_*.py` (this rule's own fixture corpus) self-pair their
        # `*_defaults` writer with their `from_*` reader — a real module
        # that merely happens to define both shapes is never guessed at
        for f in project.files:
            if not os.path.basename(f.rel).startswith("reg005_"):
                continue
            writer = next(
                (n.name for n in ast.walk(f.tree)
                 if isinstance(n, ast.FunctionDef)
                 and n.name.endswith("_defaults")
                 and _dict_literal_keys(n) is not None), None)
            reader = next(
                (n.name for n in ast.walk(f.tree)
                 if isinstance(n, ast.FunctionDef)
                 and n.name.startswith("from_")), None)
            if writer and reader:
                specs.append((f.rel, writer, f.rel, reader))

        for wfile, wfunc, rfile, rfunc in specs:
            wctx = project.by_rel.get(wfile)
            rctx = project.by_rel.get(rfile)
            if wctx is None or rctx is None:
                continue  # partial scans (e.g. --diff on one file)
            wfn = _function_def(wctx.tree, wfunc)
            rfn = _function_def(rctx.tree, rfunc)
            if wfn is None or rfn is None:
                continue
            written = _dict_literal_keys(wfn)
            if written is None:
                continue
            read = _get_call_keys(rfn)
            for key in sorted(written - read):
                out.append(self.finding(
                    wctx, wfn,
                    f"config key `{key}` is written by {wfunc}() but "
                    f"never read/validated by {rfile}:{rfunc}()"))
            for key in sorted(read - written):
                out.append(self.finding(
                    rctx, rfn,
                    f"config key `{key}` is read by {rfunc}() but not "
                    f"written back as a default by {wfile}:{wfunc}()"))
        return out
