"""The language-model layers' time inside the train step, from the trace.

New trace arithmetic of the language-model cells: the self time of the
train-step programs' ops whose ``op_name`` carries one of the layers' own
scopes (``attn.core``, ``moe.route``, ``moe.experts``, ``moe.gmm``,
``lm.head``, ``lm.xent``; declared in the program's analysis/registry.py),
forward, recomputed forward and backward alike, scaled like
``trace_scopes._ms``: a share of the step programs' self time times the
accepted ``step_device_ms``.  Everything is read through
``trace_scopes.load(facts)``; where that gives nothing (no trace, no
``hlo_scopes.json``, a program without these scopes: the parent of the PR
that added them) every reader gets None and raises nothing.
"""

from __future__ import annotations

import re
import sys
import traceback

_PEAK_BF16 = 197e12     # peaks.py DEVICE_PEAKS["TPU v5 lite"]


def _sibling(name):
    """``<name>.py`` beside this file, through ``run.py``'s loader."""
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    return (sys.modules.get("benchmark_" + name)
            or run.load_module("", name))


def scope_seconds(facts, names):
    """Seconds of a train step spent in ops under any scope of ``names``
    (a path component of the op's ``op_name``), or None."""
    try:
        out = _sibling("trace_scopes").load(facts)
        if out is None or not out["resolved"] or not out["step"]["total"]:
            return None
        pat = re.compile(r"(?:^|/)(?:%s)(?=/|$)" % "|".join(
            re.escape(n) for n in names))
        part = sum(t for _label, (t, scope, _src) in out["ops"]
                   if scope and pat.search(scope)) * out["devices"]
        if part <= 0:
            return None
        return out["step_device_s"] * part / out["step"]["total"]
    except Exception:  # an unreadable trace: the metric is left out
        traceback.print_exc()
        return None


def scope_ms(facts, names):
    s = scope_seconds(facts, names)
    return None if s is None else 1e3 * s


# the step's split as PERF.md section 5 gives it: every scope the stack
# opens (moe.gmm lies inside moe.experts) and the step's own phases
SPLIT = (("lm.embed",), ("attn.proj",), ("attn.core",), ("ffn.dense",),
         ("moe.route",), ("moe.experts",), ("moe.gmm",), ("moe.shared",),
         ("lm.head",), ("lm.xent",), ("step.optimizer",), ("step.metrics",))


def print_split(facts):
    """One line a scope, ms of a train step (forward, recomputed forward
    and backward together); prints nothing where there is no scope file."""
    if facts.get("_lm_split_printed"):
        return
    facts["_lm_split_printed"] = True
    rows = [(names[0], scope_ms(facts, names)) for names in SPLIT]
    if any(ms is not None for _n, ms in rows):
        print("[lm] train step by the stack's own scopes, ms a step: "
              + ", ".join(f"{n} {ms:.2f}" for n, ms in rows
                          if ms is not None), flush=True)


def attention_core_mxu_pct(facts):
    """FLOPs of the visible pairs of a mean train step over the attention
    kernels' time, as a share of the bf16 peak."""
    try:
        lm = facts.get("lm")
        s = scope_seconds(facts, ("attn.core",))
        if not lm or not s:
            return None
        print_split(facts)
        counts = _sibling("lm_counts")
        flops = sum(counts.attention_core_flops(
            k["pairs_per_step"], k["heads_summed"], lm["head_dim"])
            for k in lm["attention"].values())
        return 100.0 * flops / s / _PEAK_BF16
    except Exception:
        traceback.print_exc()
        return None


def grouped_mxu_pct(facts):
    """FLOPs of the grouped products over the slots really routed to a
    held expert (step records' ``moe`` block, counted epochs) over the
    grouped products' time, as a share of the bf16 peak."""
    try:
        lm, epochs = facts.get("lm"), facts.get("epochs") or []
        s = scope_seconds(facts, ("moe.gmm",))
        steps = sum(e.get("steps", 0) for e in epochs)
        slots = sum(e.get("moe_slots_held") or 0 for e in epochs)
        if not lm or not s or not steps or not slots:
            return None
        counts = _sibling("lm_counts")
        flops = counts.grouped_ffn_flops(
            slots / steps, lm["hidden_size"], lm["moe_intermediate_size"])
        return 100.0 * flops / s / _PEAK_BF16
    except Exception:
        traceback.print_exc()
        return None
