"""Inside the step: the device trace split by what the PROGRAM calls things.

``trace_reduce.py`` reads a trace by what the profiler says an op is (its
opcode).  This helper reads it by the program's own names, which PR 23 gave
it: the step's phase scopes (``step.loss`` -> forward ``jvp(...)`` and
backward ``transpose(jvp(...))``, ``step.optimizer``, ``step.metrics``,
``step.guard``, ``step.eval``, ``comm.*``) with the flax module path below
them, the kernels' names (a Mosaic custom call is now the instruction
``%gather_mul_seg_fwd.63``), and the trainer's host regions, which
``JaxProfilerTracer`` writes as ``TraceAnnotation``s into the ``/host:CPU``
plane of the same file, on the device planes' clock.

What one trace of this program holds (read by hand on the v5e, PR 23): an
``XLA Ops`` event carries its instruction's text and three timing stats,
and NO ``op_name`` (the driver traces with ``enable_hlo_proto=False``).  So
the scope comes from ``hlo_scopes.json``, which the program writes beside
its telemetry JSONL while its regions are annotated
(hydragnn_tpu/telemetry/hlo_scopes.py): per compiled executable,
instruction -> [first result shape, op_name, inherited, source line].
The join is on the instruction's name; a program has one executable per
bucket shape, and the shapes tell them apart.  A program that writes no
such file (the parent of PR 23) leaves every scope metric out: ``read``
returns None.

Shares are of the SELF time (``trace_reduce.nest``) of the ops inside the
train-step programs' executions inside the window, scaled by the accepted
``step_device_s``, so the phases add up to ``step_device_ms`` and nobody
derives step boundaries a second time.  The file is parsed once a run
(memoised on ``facts``); the arithmetic is plain functions on plain lists
(tests/benchmark/test_trace_scopes.py).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
import traceback

PHASE = re.compile(r"(?:^|/)((?:step|comm)\.[a-z_]+)(?=/|$)")
# the step phases as the metrics name them
PHASE_OF = {"step.optimizer": "optimizer", "step.metrics": "metrics",
            "step.guard": "guard", "step.eval": "eval"}
MIN_GAP_NS = 1e6            # an idle gap worth a name: 1 ms
LEGACY_REGIONS = ("train", "validate", "test", "metrics_fetch")


def _sibling(name):
    """``<name>.py`` beside this file, through the loader ``run.py`` put
    into ``sys.modules`` (``__main__`` as the command, ``benchmark_run``
    under the tests)."""
    mod = sys.modules.get("benchmark_" + name)
    if mod is None:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        mod = run.load_module("", name)
    return mod


# -- arithmetic on plain values -----------------------------------------------

def classify(scope, depth=3):
    """An ``op_name`` -> (phase, module path cut at ``depth``).  Phase is
    ``fwd`` / ``bwd`` (``step.loss`` without / with a ``transpose(`` below
    it), ``optimizer`` / ``metrics`` / ``guard`` / ``eval``, a ``comm.*``
    name, or None where no declared scope is in it.  XLA joins the names of
    ops it merged; the first scope counts, and the path stops at the next."""
    m = PHASE.search(scope or "")
    if not m:
        return None, ""
    name, rest = m.group(1), scope[m.end():].strip("/")
    nxt = PHASE.search(rest)
    if nxt:
        rest = rest[:nxt.start()]
    parts = [p for p in rest.split("/") if p]
    if name == "step.loss":
        phase = "bwd" if "transpose(" in rest else "fwd"
        # the jvp(...) / transpose(jvp(...)) wrapper is the phase, not a
        # module
        parts = [p for p in parts
                 if not p.startswith(("jvp(", "transpose("))]
    else:
        phase = PHASE_OF.get(name, name)
    # a path ends where the primitives begin: keep module-like parts
    return phase, "/".join(parts[:depth])


def kernel_of(label, opcode):
    """The kernel's name for a Mosaic custom call (its instruction is
    named by ``pl.pallas_call(name=...)``: ``gather_mul_seg_fwd.63``),
    else None.  XLA's own custom calls are ``custom-call.N``."""
    if opcode != "custom-call":
        return None
    stem = label.split(" ", 1)[0].rsplit(".", 1)[0]
    return None if stem == "custom-call" else stem


def pick_executable(seen, executables):
    """Of one program's compiled ``executables`` (each ``{instruction:
    [shape, scope, inherited]}``), the one that ``seen`` — ``{instruction:
    shape}`` as the trace shows them — was run from: the most instructions
    with that name AND that result shape.  None when nothing matches."""
    best, best_hits = None, 0
    for ex in executables:
        hits = sum(1 for name, shape in seen.items()
                   if name in ex and ex[name][0] == shape)
        if hits > best_hits:
            best, best_hits = ex, hits
    return best


def split_step(rows):
    """``rows``: (self time, scope or None, inherited, kernel or None) per
    op of the train-step programs.  Returns the step's self time by phase,
    by (phase, module path), by kernel, and in all; ``unnamed`` holds what
    no declared scope claims, ``inherited`` what took an operand's."""
    out = {"total": 0.0, "phase": {}, "scope": {}, "kernel": {},
           "unnamed": 0.0, "inherited": 0.0}
    for own, scope, inherited, kernel in rows:
        if own <= 0:
            continue
        out["total"] += own
        phase, path = classify(scope)
        if phase is None:
            out["unnamed"] += own
            phase = "unnamed"
        elif inherited:
            out["inherited"] += own
        out["phase"][phase] = out["phase"].get(phase, 0.0) + own
        key = (phase, path)
        out["scope"][key] = out["scope"].get(key, 0.0) + own
        if kernel:
            out["kernel"][kernel] = out["kernel"].get(kernel, 0.0) + own
    return out


def label_gap_by_region(gap, regions):
    """The host region the device idled under: of the trainer thread's
    ``regions`` (name, start, end; they nest), the one whose SELF time —
    its span minus the regions inside it — covers most of ``gap``, so an
    enclosing region never wins over the one the host was really in.
    Returns (name, covered) or ("none", 0)."""
    reduce = _sibling("trace_reduce")
    spans = [("", "", s, e) for _n, s, e in regions]
    own_cover = [max(0.0, min(e, gap[1]) - max(s, gap[0]))
                 for _n, s, e in regions]
    _own, parent = reduce.nest(spans)
    cover = list(own_cover)
    for i, p in enumerate(parent):
        if p >= 0:
            cover[p] -= own_cover[i]
    best = max(range(len(regions)), key=lambda i: cover[i], default=None)
    if best is None or cover[best] <= 0:
        return "none", 0.0
    return regions[best][0], cover[best]


def region_seconds(facts, names, lo=None, hi=None):
    """(sum of seconds, count) of the driver's ``facts["spans"]`` named in
    ``names`` that began in [lo, hi) on the benchmark's clock; (None, 0)
    where the program opened no such region."""
    took = [b - a for n, a, b in facts.get("spans") or ()
            if n in names and (lo is None or a >= lo)
            and (hi is None or a < hi)]
    return (sum(took), len(took)) if took else (None, 0)


# -- the file -----------------------------------------------------------------

def _declared_regions():
    try:
        from hydragnn_tpu.analysis.registry import SPAN_NAMES

        return set(SPAN_NAMES) | set(LEGACY_REGIONS)
    except ImportError:
        return set(LEGACY_REGIONS)


def _load_xplane(path):
    """Device planes as ``trace_reduce`` shapes them, the trainer
    thread's declared regions from the host plane (name, start, end), all
    in nanoseconds since the profile's start, and that start in unix
    nanoseconds (or None)."""
    from jax.profiler import ProfileData

    reduce = _sibling("trace_reduce")
    declared = _declared_regions()
    devices, host_lines, start_unix = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start_unix = next((int(v) for k, v in plane.stats
                               if k == "profile_start_time"), None)
        m = reduce.DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == reduce.OPS_LINE:
                    parsed = {}
                    for ev in line.events:
                        if ev.name not in parsed:
                            parsed[ev.name] = reduce.parse_instruction(
                                ev.name)
                        label, opcode = parsed[ev.name]
                        dev["ops"].append(
                            (label, opcode, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
                elif line.name == reduce.MODULES_LINE:
                    dev["modules"] += [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                regions = [(ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events if ev.name in declared]
                if regions:
                    host_lines.append(regions)
    # the trainer thread is the one that opens the epochs' regions
    trainer = max(host_lines, default=[], key=lambda rs: sum(
        1 for n, _s, _e in rs if n in LEGACY_REGIONS))
    return devices, trainer, start_unix


def _load_scopes(trace_dir):
    """``{program name: [executable maps]}`` from the run's
    ``hlo_scopes.json``, or {}."""
    logs = os.path.join(os.path.dirname(trace_dir), "logs")
    paths = glob.glob(os.path.join(logs, "**", "hlo_scopes.json"),
                      recursive=True)
    programs = {}
    for path in paths:
        with open(path) as f:
            for prog in json.load(f)["programs"]:
                programs.setdefault(prog["name"], []).append(
                    prog["instructions"])
    return programs


def _instruction(label):
    """(instruction name, first result shape) of a ``trace_reduce``
    label ``name opcode shape``."""
    parts = label.split(" ")
    return parts[0], (parts[2] if len(parts) > 2 else "")


def reduce_device(ops, modules, programs, lo, hi, train_regex):
    """One device: the rows ``split_step`` takes for the train-step
    programs, the self time of ``step.eval`` ops anywhere, and the train
    step's ops by label with their scope and source line."""
    reduce = _sibling("trace_reduce")
    own, _parent = reduce.nest(ops)
    pat = re.compile(train_regex)
    order = sorted(range(len(ops)), key=lambda i: ops[i][2])
    begins = [ops[i][2] for i in order]
    by_module = {}                      # module event name -> op indexes
    for name, m_lo, m_hi in modules:
        if m_hi <= lo or m_lo >= hi:
            continue
        inside = by_module.setdefault(name, [])
        for i in order[bisect.bisect_left(begins, m_lo):
                       bisect.bisect_right(begins, m_hi)]:
            if ops[i][3] <= m_hi and ops[i][2] >= lo and ops[i][3] <= hi:
                inside.append(i)
    rows, per_op, eval_self, resolved = [], {}, 0.0, False
    for name, inside in by_module.items():
        program = name.split("(", 1)[0]
        seen = dict(_instruction(ops[i][0]) for i in inside)
        ex = pick_executable(seen, programs.get(program, ()))
        resolved = resolved or ex is not None
        is_train = bool(pat.search(name))
        for i in inside:
            instr, _shape = _instruction(ops[i][0])
            _s, scope, inherited, source = (
                (ex or {}).get(instr) or ("", None, 0, ""))
            if classify(scope)[0] == "eval":
                eval_self += max(own[i], 0.0)
            if is_train:
                rows.append((own[i], scope, inherited,
                             kernel_of(ops[i][0], ops[i][1])))
                if own[i] > 0:
                    t = per_op.get(ops[i][0], (0.0,))[0]
                    per_op[ops[i][0]] = (t + own[i], scope, source)
    return {"rows": rows, "eval_self": eval_self, "ops": per_op,
            "resolved": resolved}


def load(facts):
    """Everything the scope metrics read, computed once a run and kept on
    ``facts``; None where there is no trace, no device op, or no scope
    file to join (the parent of PR 23).  Prints the step's table."""
    if "_trace_scopes" in facts:
        return facts["_trace_scopes"]
    facts["_trace_scopes"] = None
    tr = facts.get("trace")
    if not tr or not tr.get("step_device_s") or not facts.get("trace_dir"):
        return None
    reduce = _sibling("trace_reduce")
    path = reduce.find_xplane(facts["trace_dir"])
    if path is None:
        return None
    devices, regions, start_unix = _load_xplane(path)
    programs = _load_scopes(facts["trace_dir"])
    if start_unix is None or not devices:
        return None
    shift = facts["mono_to_unix_ns"] - start_unix
    lo = max(0.0, facts["trace_window"][0] * 1e9 + shift)
    hi = facts["trace_window"][1] * 1e9 + shift
    per_device = [
        reduce_device(d["ops"], d["modules"], programs, lo, hi,
                      facts["train_module_regex"])
        for _i, d in sorted(devices.items()) if d["ops"]]
    n = len(per_device)
    if not n:
        return None
    step = split_step([r for d in per_device for r in d["rows"]])
    gaps = []
    for _i, d in sorted(devices.items()):
        busy = reduce.clip(reduce.union(
            [(s, e) for _l, _c, s, e in d["ops"]]), lo, hi)
        for g in reduce.gaps(busy, lo, hi):
            if g[1] - g[0] >= MIN_GAP_NS:
                gaps.append((g[1] - g[0],)
                            + label_gap_by_region(g, regions))
    ops = {}
    for d in per_device:
        for label, (t, scope, source) in d["ops"].items():
            ops[label] = (ops.get(label, (0.0,))[0] + t / n, scope, source)
    out = {
        "resolved": any(d["resolved"] for d in per_device),
        "devices": n, "step": step,
        "step_device_s": tr["step_device_s"],
        "eval_self_s": sum(d["eval_self"] for d in per_device) / n * 1e-9,
        "busy_s": tr["busy_s"],
        "gaps": sorted(gaps, reverse=True),
        "ops": sorted(ops.items(), key=lambda kv: -kv[1][0]),
    }
    facts["_trace_scopes"] = out
    _print_tables(out)
    return out


def _ms(out, part):
    """Self time ``part`` of the step as milliseconds of a step."""
    total = out["step"]["total"]
    return 1e3 * out["step_device_s"] * part / total if total else None


def _print_tables(out):
    say = lambda s: print(f"[scopes] {s}", flush=True)  # noqa: E731
    step = out["step"]
    if not out["resolved"]:
        say("no hlo_scopes.json matches this trace: scope metrics left out")
    total = step["total"] or 1.0
    say(f"train step {1e3 * out['step_device_s']:.3f} ms a step on "
        f"{out['devices']} device(s); self time by phase:")
    for phase, t in sorted(step["phase"].items(), key=lambda kv: -kv[1]):
        say(f"  {phase:<12} {_ms(out, t):8.3f} ms  {100 * t / total:5.1f} %")
    say(f"  (named {100 * (1 - step['unnamed'] / total):.1f} %, of which "
        f"by an operand's scope {100 * step['inherited'] / total:.1f} %)")
    say("by phase x module path (depth 3), ms a step, share:")
    for (phase, path), t in sorted(step["scope"].items(),
                                   key=lambda kv: -kv[1])[:28]:
        say(f"  {phase:<10} {path or '-':<44} {_ms(out, t):8.3f} "
            f"{100 * t / total:5.1f} %")
    say("by phase x source line, ms a step, share:")
    by_line = {}
    for _label, (t, scope, source) in out["ops"]:
        key = (classify(scope)[0] or "unnamed", source or "-")
        by_line[key] = by_line.get(key, 0.0) + t * out["devices"]
    for (phase, source), t in sorted(by_line.items(),
                                     key=lambda kv: -kv[1])[:10]:
        say(f"  {phase:<10} {source:<44} {_ms(out, t):8.3f} "
            f"{100 * t / total:5.1f} %")
    say("kernels, ms a step:")
    for name, t in sorted(step["kernel"].items(), key=lambda kv: -kv[1]):
        say(f"  {name:<28} {_ms(out, t):8.3f} {100 * t / total:5.1f} %")
    say("largest ops of the train step (self time in the window, s; "
        "phase; module path; source line):")
    for label, (t, scope, source) in out["ops"][:12]:
        phase, path = classify(scope, depth=4)
        say(f"  {label:<44} {t * 1e-9:9.6f} {phase or 'unnamed':<9} "
            f"{path:<36} {source}")
    say(f"idle gaps >= 1 ms: {len(out['gaps'])}")
    for length, name, covered in out["gaps"][:8]:
        say(f"  {length * 1e-6:7.3f} ms under {name} "
            f"({covered * 1e-6:.3f} ms of it)")


# -- what the readers ask for -------------------------------------------------

def read(facts, what, arg=None):
    """One metric, or None where its source is not there.  Never raises:
    a reader of this PR also runs over the parent's program."""
    try:
        return _read(facts, what, arg)
    except Exception:  # an unreadable trace: the metric is left out
        traceback.print_exc()
        return None


def _read(facts, what, arg):
    epochs = facts.get("epochs") or []
    if what == "region_ms":             # mean per call, counted epochs
        if not epochs:
            return None
        took, n = region_seconds(facts, arg, epochs[0]["t0"],
                                 epochs[-1]["t1"])
        return None if took is None else 1e3 * took / n
    if what == "region_ms_per_epoch":   # sum per counted epoch
        if not epochs:
            return None
        took, _n = region_seconds(facts, arg, epochs[0]["t0"],
                                  epochs[-1]["t1"])
        return None if took is None else 1e3 * took / len(epochs)
    if what == "setup_region_s":        # before the window opens
        if not epochs:
            return None
        return region_seconds(facts, arg, None, epochs[0]["t0"])[0]
    if what == "setup_epoch0_s":
        begun = [a for n, a, _b in facts.get("spans") or ()
                 if n == "train"]
        return epochs[0]["t0"] - min(begun) if begun and epochs else None
    out = load(facts)
    if out is None or not out["resolved"] or not out["step"]["total"]:
        return None
    step = out["step"]
    if what == "phase_ms":
        return _ms(out, sum(step["phase"].get(p, 0.0) for p in arg))
    if what == "named_pct":
        return 100.0 * (1.0 - step["unnamed"] / step["total"])
    if what == "kernel_ms":
        return _ms(out, sum(step["kernel"].get(k, 0.0) for k in arg))
    if what == "eval_share_pct":
        return (100.0 * out["eval_self_s"] / out["busy_s"]
                if out["busy_s"] else None)
    raise KeyError(what)
