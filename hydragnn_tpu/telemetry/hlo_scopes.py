"""Which scope does a compiled instruction belong to?  The map a device
trace needs and does not carry (docs/TELEMETRY.md "Tracing").

A profiler trace of the TPU names each executed op by its HLO instruction
(``%fusion.892 = f32[128]{0} fusion(...)``) and, unless the profiler is
asked for the whole HLO proto, nothing else: the ``op_name`` metadata —
``jit(scan_step)/while/body/closed_call/step.loss/jvp(SCFStack)/
encoder_conv_3/...``, where the step's phases (train/trainer.py:phase)
and the flax module path live — stays in the executable.  So while the
trainer's regions are annotated for a profiler (``utils/tracer``
``jax_annotations``), it notes the step programs it dispatches and writes
``hlo_scopes.json`` beside the telemetry JSONL once, after the first
epoch; whoever reads the trace joins on the instruction name.

    {"programs": [{"name": "jit_scan_step", "instructions": {
        "fusion.892": ["f32[128]", "<op_name>", 0, "models/schnet.py:111"],
        ...}}, ...]}

One entry per compiled executable (a program has one per bucket shape);
the first result shape tells executables of one program apart.  The third
field is 1 where the instruction has no ``op_name`` of its own (the copies,
slices and tuple plumbing the compiler inserts) and scope and source line
are those of the nearest operand that has one.  The fourth is the
innermost frame of the instruction's ``stack_frame_id`` in the text's own
frame tables: the line of this package that bound the primitive.
Instructions inside fused computations never run on their own and are left
out; a fusion without ``op_name`` takes its root's.

The same executables state what they need on a device
(``memory_analysis()``: arguments, outputs, what aliases, temporaries,
code), which the allocator's ``memory_stats()`` does not show of a
program's temporaries: :meth:`StepPrograms.write` hands those bytes on,
one ``program_memory`` record a program.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from hydragnn_tpu.utils import tracer

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _source_lines(lines) -> Dict[str, str]:
    """``{stack_frame_id: "models/schnet.py:111"}`` from the frame tables
    at the head of a module's text (FileNames, FileLocations,
    StackFrames): the innermost frame that lies in this package, or, for
    a primitive bound outside it, the innermost of all."""
    tables: Dict[str, Dict[str, str]] = {t: {} for t in _TABLES}
    table = None
    for line in lines:
        if line in tables:
            table = tables[line]
        elif _COMPUTATION.match(line):
            break
        elif table is not None:
            m = _TABLE_ROW.match(line)
            if m:
                table[m.group(1)] = m.group(2)

    where, parent = {}, {}          # frame -> its "file:line", its parent
    for frame, row in tables["StackFrames"].items():
        loc = re.search(r"file_location_id=(\d+)", row)
        loc = tables["FileLocations"].get(loc.group(1), "") if loc else ""
        name = re.search(r"file_name_id=(\d+)", loc)
        line_no = re.search(r" line=(\d+)", loc)
        up = re.search(r"parent_frame_id=(\d+)", row)
        path = tables["FileNames"].get(
            name.group(1), "").strip('"') if name else ""
        where[frame] = (f"{path}:{line_no.group(1)}"
                        if path and line_no else "")
        parent[frame] = up.group(1) if up else "0"
    out = {}
    for frame in where:
        chain, at = [], frame
        while at in where and at not in chain:
            chain.append(at)
            at = parent[at]
        ours = next((where[f].split("hydragnn_tpu/", 1)[1] for f in chain
                     if "hydragnn_tpu/" in where[f]), None)
        out[frame] = ours or where[frame]
    return out


def _whole_instructions(lines):
    """``lines`` with an instruction's continuation lines joined to it.  A
    kernel that carries a multi-line attribute (the splash attention
    kernels' ``xprof_metadata``) prints its custom call over several
    lines, the last of which begins with ``}}``: read as the end of the
    computation, it hid every instruction after it (PERF.md, PR 30)."""
    whole = None
    for line in lines:
        starts = (_INSTRUCTION.match(line) or _COMPUTATION.match(line)
                  or line.rstrip() == "}" or not line.strip())
        if whole is not None and not starts:
            whole += " " + line
            continue
        if whole is not None:
            yield whole
        whole = line
    if whole is not None:
        yield whole


def instruction_scopes(hlo_text: str) -> Dict[str, List[Any]]:
    """``{instruction: [first result shape, scope, inherited, source]}``
    for every instruction of ``hlo_text`` (a compiled module's
    ``as_text()``) that can run as an op of its own."""
    rows: Dict[str, Tuple[str, str, Tuple[str, str], str, List[str]]] = {}
    roots: Dict[str, Tuple[str, str]] = {}  # computation -> its root's
    fused = set()
    computation = None
    lines = hlo_text.splitlines()
    sources = _source_lines(lines)
    for line in _whole_instructions(lines):
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        if line.rstrip() == "}":
            computation = None
            continue
        m = _INSTRUCTION.match(line) if computation else None
        if not m:
            continue
        is_root, name, rest = m.groups()
        body = rest.split(", metadata=", 1)[0]
        scope = _OP_NAME.search(rest)
        frame = _FRAME_ID.search(rest)
        # (op_name, source line): what the instruction says of itself
        scope = (scope.group(1) if scope else "",
                 sources.get(frame.group(1), "") if frame else "")
        opcode = _OPCODE.search(" " + body)
        shape = _SHAPE.search(body)
        calls = _CALLS.search(body)
        if opcode and opcode.group(1) == "fusion" and calls:
            fused.add(calls.group(1))
        if is_root:
            roots[computation] = scope
        operands = _OPERAND.findall(body.split("(", 1)[1]) \
            if "(" in body else []
        rows[name] = (computation, shape.group(0) if shape else "", scope,
                      calls.group(1) if calls else "", operands)

    none = ("", "")
    # what each instruction says of itself, a fusion through its root
    said = {name: (scope if scope[0] or calls not in fused
                   else roots.get(calls, none))
            for name, (_c, _shape, scope, calls, _ops) in rows.items()}

    def inherited(name: str, seen: set) -> Tuple[str, str]:
        """What the nearest operand that has a scope says of itself."""
        for op in rows[name][4]:
            if op in rows and op not in seen:
                seen.add(op)
                found = said[op] if said[op][0] else inherited(op, seen)
                if found[0]:
                    return found
        return none

    out: Dict[str, List[Any]] = {}
    for name, (computation, shape, _scope, _calls, _ops) in rows.items():
        if computation in fused:
            continue
        scope, borrowed = said[name], 0
        if not scope[0]:
            scope, borrowed = inherited(name, {name}), 1
        out[name] = [shape, scope[0], borrowed if scope[0] else 0, scope[1]]
    return out


_MEMORY_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
    ("peak_bytes", "peak_memory_in_bytes"))


def _tell_memory(compiled, name: str, on_memory) -> None:
    try:
        stats = compiled.memory_analysis()
        fields = {ours: int(getattr(stats, theirs))
                  for ours, theirs in _MEMORY_FIELDS}
    except Exception:  # graftlint: disable=ROB001 (a backend without the analysis leaves no record, the run is unaffected)
        return
    on_memory(name, **fields)


class StepPrograms:
    """Notes each step program as the trainer dispatches it — the jitted
    function and the shapes (and shardings) it was called with, one note
    per distinct signature — and writes their scopes once.  ``watch(fn)``
    returns ``fn`` behind that note-taking; after :meth:`write` the notes
    stop."""

    def __init__(self):
        self._seen: Dict[Tuple, Tuple[Callable, tuple]] = {}
        self._done = False

    def watch(self, fn: Callable) -> Callable:
        import jax

        def aval(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                # as the call lowered it: a committed array's sharding is
                # part of the program, an uncommitted one's is not (and
                # stating it would compile a second program)
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=(x.sharding
                              if getattr(x, "committed", False) else None))
            return x

        def watched(*args):
            if not self._done:
                sig = (id(fn),) + tuple(
                    (getattr(x, "shape", None), str(getattr(x, "dtype", "")))
                    for x in jax.tree_util.tree_leaves(args))
                if sig not in self._seen:
                    self._seen[sig] = (fn, jax.tree.map(aval, args))
            return fn(*args)

        return watched

    def write(self, path: str,
              on_memory: Optional[Callable[..., None]] = None) -> None:
        """Compile each noted program again from its shapes (a read of the
        persistent compile cache where that is on), parse its HLO text,
        write ``path``; ``on_memory(name, argument_bytes=...)`` gets each
        executable's ``memory_analysis()``, per device.  Best effort: a
        backend that cannot give the text leaves no file, and training
        goes on."""
        self._done = True
        programs = []
        with tracer.timer("telemetry.step_programs"):
            for fn, avals in self._seen.values():
                try:
                    compiled = fn.lower(*avals).compile()
                    text = compiled.as_text()
                except Exception:  # graftlint: disable=ROB001 (a trace aid: without the text there is no file, the run is unaffected)
                    continue
                name = re.search(r"^HloModule ([\w.\-]+)", text, re.M)
                name = name.group(1) if name else getattr(
                    fn, "__name__", "step")
                programs.append({"name": name,
                                 "instructions": instruction_scopes(text)})
                if on_memory is not None:
                    with tracer.timer("telemetry.program_memory"):
                        _tell_memory(compiled, name, on_memory)
        self._seen.clear()
        if programs:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"programs": programs}, f)
            os.replace(tmp, path)
