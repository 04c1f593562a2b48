"""From a profiler trace (.xplane.pb) to device numbers.

Two halves.  ``load_xplane`` reads the file with nothing but JAX
(``jax.profiler.ProfileData``) into plain interval lists; everything after
it is arithmetic on those lists, so tests/benchmark/test_trace_reduce.py
checks it on hand-made intervals and every PR computes the same number the
same way.

What the trace of this program looks like on the v5e (read by hand, PR 22):
one plane per chip named ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds
one event per executed HLO instruction, NAMED BY THE INSTRUCTION'S WHOLE
TEXT (``%fusion.905 = f32[105448,128]{...} fusion(...), kind=kCustom``),
with no category stat; a ``while`` (the scan over K steps, and small inner
loops) is an event that encloses its body's events, so durations nest and
only self time adds up.  ``Async XLA Ops`` holds the start-to-done spans of
asynchronous copies and collectives, ``XLA Modules`` one event per executed
program (``jit_scan_step(<fingerprint>)``).  Host threads are lines of the
plane ``/host:CPU``.  All times are nanoseconds since the profile's start,
on one clock for host and devices; the plane ``Task Environment`` carries
``profile_start_time`` in unix nanoseconds, which ties the benchmark's own
clock readings to it.

No kernel of this program has a stable name yet, so a Mosaic kernel is
found by what the trace says the op IS — its HLO opcode, ``custom-call`` —
never by a kernel's name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of the disjoint sorted list ``a`` not covered by the
    disjoint sorted list ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given the disjoint busy list."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


def label_gap(gap, host_spans):
    """The name of the host span that holds most of ``gap``; ``none`` when
    no span touches it.  ``host_spans``: (name, start, end)."""
    best, best_cover = "none", 0.0
    for name, s, e in host_spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def parse_instruction(text):
    """An ``XLA Ops`` event name -> (short label, opcode).  The name is the
    HLO instruction's text; the opcode is the first ``word(`` after the
    result type (layouts write ``T(8,128)`` after a colon or a brace, never
    after a space).  The label is ``name opcode first-result-shape``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80], ""
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    shape = _SHAPE.search(rest)
    return (f"{name.lstrip('%')} {opcode} "
            f"{shape.group(0) if shape else ''}").strip()[:80], opcode


def is_collective(opcode):
    return bool(COLLECTIVE.match(opcode))


def is_mosaic(opcode):
    return opcode == "custom-call"


def nest(ops):
    """Self time and parent of every event of one sequential line whose
    events nest (a ``while`` encloses its body).  ``ops``: (label, opcode,
    start, end).  Returns (own, parent): duration minus the part the
    children cover, and the index of the enclosing event or -1."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [ops[i][3] - ops[i][2] for i in range(len(ops))]
    parent = [-1] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][2], ops[i][3]
        while stack and ops[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][3]:
            own[stack[-1]] -= e - s
            parent[i] = stack[-1]
        stack.append(i)
    return own, parent


def train_steps(ops, parent, modules, busy, lo, hi):
    """Optimizer steps counted from the trace itself, and the device-busy
    time they took.  A scanned train program is one top-level ``while``
    that takes most of the execution and whose body runs once per step; a
    body instruction therefore repeats once per step.  Inside each
    train-module execution, take the instructions one level below that
    loop — or at top level where the trace began or ended inside the loop
    and so holds no event for it — and the most repeated of them: n
    repeats that begin inside the window bound n - 1 whole steps, first
    begin to last begin.  No edge is guessed, and an execution the trace
    cuts still counts.  A program that is not scanned repeats nothing and
    yields no step time.  Returns (steps, busy time)."""
    steps, spent = 0, 0.0
    order = sorted(range(len(ops)), key=lambda i: ops[i][2])
    begins_at = [ops[i][2] for i in order]
    for m_lo, m_hi in modules:
        inside = [i for i in order[bisect.bisect_left(begins_at, m_lo):
                                   bisect.bisect_right(begins_at, m_hi)]
                  if ops[i][3] <= m_hi]
        loops = {i for i in inside
                 if parent[i] == -1 and ops[i][1] == "while"
                 and 2 * (ops[i][3] - ops[i][2]) > m_hi - m_lo}
        begins = {}
        for i in inside:
            if ((parent[i] in loops or (parent[i] == -1 and i not in loops))
                    and lo <= ops[i][2] and ops[i][3] <= hi):
                begins.setdefault(ops[i][0], []).append(ops[i][2])
        ref = max(begins.values(), key=len, default=[])
        if len(ref) >= 2:
            steps += len(ref) - 1
            spent += total(clip(busy, min(ref), max(ref)))
    return steps, spent


# -- one device ---------------------------------------------------------------

def reduce_device(ops, modules, lo, hi, train_regex, host_spans=(),
                  n_gaps=5, async_ops=()):
    """Numbers of one device over the window [lo, hi].

    ``ops``: (label, opcode, start, end) per executed op of the sequential
    line, containers included; ``async_ops``: the same for start-to-done
    spans; ``modules``: (name, start, end) per executed program; any time
    unit, the same for all.  Returns a dict in that unit."""
    busy = clip(union([(s, e) for _n, _c, s, e in ops]), lo, hi)
    coll = clip(union([(s, e) for _n, c, s, e in list(ops) + list(async_ops)
                       if is_collective(c)]), lo, hi)
    other = clip(union([(s, e) for _n, c, s, e in ops
                        if not is_collective(c) and c not in CONTAINERS]),
                 lo, hi)
    mosaic = clip(union([(s, e) for _n, c, s, e in ops if is_mosaic(c)]),
                  lo, hi)
    pat = re.compile(train_regex)
    own_times, parent = nest(ops)
    steps, train_busy = train_steps(
        ops, parent, [(s, e) for n, s, e in modules
                      if pat.search(n) and e > lo and s < hi],
        busy, lo, hi)
    per_op = {}
    for (n, _c, s, e), own in zip(ops, own_times):
        if s >= lo and e <= hi and own > 0:
            per_op[n] = per_op.get(n, 0.0) + own
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n_gaps]
    return {
        "window": hi - lo,
        "busy": total(busy),
        "mosaic": total(mosaic),
        "collective": total(coll),
        "collective_exposed": total(subtract(coll, other)),
        "train_steps": steps,
        "train_busy": train_busy,
        "ops": sorted(per_op.items(), key=lambda kv: -kv[1]),
        "gaps": [(label_gap(g, host_spans), g[1] - g[0]) for g in idle],
    }


def combine(devices, n_ops=10, n_gaps=5, unit=1e-9):
    """Average the per-device dicts of ``reduce_device`` over the chips
    used and convert to seconds (``unit`` = seconds per trace time unit).
    Returns None when no device ran an op."""
    devices = [d for d in devices if d["busy"] > 0]
    if not devices:
        return None
    n = len(devices)
    mean = lambda key: sum(d[key] for d in devices) / n * unit  # noqa: E731
    ops = {}
    for d in devices:
        for name, t in d["ops"]:
            ops[name] = ops.get(name, 0.0) + t / n * unit
    steps = sum(d["train_steps"] for d in devices)
    all_gaps = sorted((g for d in devices for g in d["gaps"]),
                      key=lambda g: -g[1])[:n_gaps]
    return {
        "devices": n,
        "window_s": mean("window"),
        "busy_s": mean("busy"),
        "mosaic_s": mean("mosaic"),
        "collective_s": mean("collective"),
        # device 0 is the one the issue names; the chips run in lockstep
        "collective_exposed_s": devices[0]["collective_exposed"] * unit,
        "train_steps": steps // n if n else 0,
        "step_device_s": (sum(d["train_busy"] for d in devices) * unit
                          / steps if steps else None),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:n_ops],
        "idle_gaps": [(label, t * unit) for label, t in all_gaps],
    }


# -- the file -----------------------------------------------------------------

def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path):
    """The trace as plain lists: ``devices`` {id: {"ops", "async",
    "modules"}} in
    the shapes ``reduce_device`` takes (nanoseconds since profile start),
    ``start_unix_ns`` (or None)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, start_unix = {}, None
    for plane in data.planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    start_unix = int(v)
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "async": [], "modules": []})
            if line.name in (OPS_LINE, ASYNC_LINE):
                into = dev["ops" if line.name == OPS_LINE else "async"]
                parsed = {}
                for ev in line.events:
                    if ev.name not in parsed:
                        parsed[ev.name] = parse_instruction(ev.name)
                    label, opcode = parsed[ev.name]
                    into.append((label, opcode, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    dev["modules"].append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    return {"devices": devices, "start_unix_ns": start_unix}


def reduce_run(facts):
    """The driver's facts (trace_dir, trace_window and spans on
    time.monotonic, mono_to_unix_ns, train_module_regex) -> the dict of
    ``combine``, or None when there is no trace or no device op in it."""
    if not facts.get("trace_dir") or not facts.get("trace_window") \
            or facts["trace_window"][1] is None:
        return None
    path = find_xplane(facts["trace_dir"])
    if path is None:
        return None
    trace = load_xplane(path)
    if not trace["devices"] or trace["start_unix_ns"] is None:
        return None
    # the benchmark's clock -> trace time
    shift = facts["mono_to_unix_ns"] - trace["start_unix_ns"]
    lo = max(0.0, facts["trace_window"][0] * 1e9 + shift)
    hi = facts["trace_window"][1] * 1e9 + shift
    host_spans = [(n, a * 1e9 + shift, b * 1e9 + shift)
                  for n, a, b in facts["spans"]]
    per_device = [
        reduce_device(d["ops"], d["modules"], lo, hi,
                      facts["train_module_regex"], host_spans,
                      async_ops=d["async"])
        for _i, d in sorted(trace["devices"].items())]
    return combine(per_device)
