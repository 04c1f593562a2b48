"""The untied head matrix and the cross-entropy (``lm.head`` +
``lm.xent``), forward and backward, milliseconds of a train step
(trace_lm.py)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("lm.head", "lm.xent"))
    except Exception:  # a reader never ends a run
        return None
