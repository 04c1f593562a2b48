"""The program's own build and memory records, read from the run's
telemetry JSONL.

Since PR 35 the program under test writes one ``program`` event for each
program JAX builds (name, ``t_start`` .. ``t`` in unix seconds,
``trace_s``, ``lower_s``, ``build_s``, ``cache`` hit / miss / off,
``cache_load_s``, and the host region, epoch and step that caused the
build) and, while its regions are annotated for a profiler, one
``program_memory`` event for each step program (the executable's
``memory_analysis()``, per device).  The files are found the way
``trace_scopes.py`` finds ``hlo_scopes.json``: beside the trace directory,
under the run's ``logs``.

*Set-up* is every ``program`` record that ended before the window opened
(``facts["epochs"][0]["t0"]``, brought to unix time through
``facts["mono_to_unix_ns"]``): what a driver builds after the job (its
comparison against the reference) is left out.  A program that writes no
such records, as the parent of that PR does not, gives every reader None.
Nothing of the program is imported.
"""

import glob
import json
import os
import re
import traceback


def load(facts):
    """``{"programs": [...], "memory": [...]}`` of the run, or None where
    the run left no telemetry to find or holds no such record."""
    if "_program_records" in facts:
        return facts["_program_records"]
    out = None
    trace_dir = facts.get("trace_dir")
    if trace_dir:
        logs = os.path.join(os.path.dirname(trace_dir), "logs")
        found = {"programs": [], "memory": []}
        for path in sorted(glob.glob(
                os.path.join(logs, "**", "events*.jsonl"), recursive=True)):
            with open(path) as f:
                for line in f:
                    if '"program' not in line:
                        continue
                    rec = json.loads(line)
                    if rec.get("event") == "program":
                        found["programs"].append(rec)
                    elif rec.get("event") == "program_memory":
                        found["memory"].append(rec)
        if found["programs"] or found["memory"]:
            out = found
    facts["_program_records"] = out
    return out


def setup_programs(facts):
    """The ``program`` records that ended before the window opened, or
    None."""
    found = load(facts)
    epochs = facts.get("epochs") or []
    if not found or not found["programs"] or not epochs:
        return None
    t_open = (epochs[0]["t0"] * 1e9 + facts["mono_to_unix_ns"]) * 1e-9
    return [r for r in found["programs"] if r["t"] < t_open]


def step_memory(facts):
    """The ``program_memory`` record of the train-step program with the
    most temporaries (one record a bucket shape), or None.  The CPU
    compiler's temporaries say nothing of the chip's: where the backend
    reports no memory (``memory_peak_bytes`` None) there is nothing to
    read, as for ``hbm_peak_gb``."""
    if not facts.get("memory_peak_bytes"):
        return None
    found = load(facts)
    if not found:
        return None
    pat = re.compile(facts["train_module_regex"])
    steps = [r for r in found["memory"] if pat.search(r["name"])]
    return max(steps, key=lambda r: r["temp_bytes"]) if steps else None


def _read(facts, what):
    if what in ("hbm_step_temp_gb", "hbm_step_need_gb"):
        rec = step_memory(facts)
        if rec is None:
            return None
        if what == "hbm_step_temp_gb":
            return rec["temp_bytes"] / 1e9
        return (rec["argument_bytes"] + rec["output_bytes"]
                - rec["alias_bytes"] + rec["temp_bytes"]
                + rec["generated_code_bytes"]) / 1e9
    built = setup_programs(facts)
    if built is None:
        return None
    if what == "setup_programs_built":
        return len(built)
    if what == "setup_trace_lower_s":
        return sum(r["trace_s"] + r["lower_s"] for r in built)
    if what == "setup_compile_s":       # what XLA compiled: no cache read
        return sum(r["build_s"] - r["cache_load_s"] for r in built
                   if r["cache"] in ("miss", "off"))
    if what == "setup_cache_load_s":
        return sum(r["cache_load_s"] for r in built if r["cache"] == "hit")
    raise KeyError(what)


def read(facts, what):
    """One metric, or None where its records are not there.  Never raises:
    these readers also run over a program that writes no such record."""
    try:
        return _read(facts, what)
    except Exception:  # unreadable records: the metric is left out
        traceback.print_exc()
        return None
