"""The routed experts of one rank (``moe.route`` + ``moe.experts``:
router, top-k, sort, dispatch gather, grouped products, combine), forward,
recomputed forward and backward, milliseconds of a train step summed over
the expert layers (trace_lm.py)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("moe.route", "moe.experts"))
    except Exception:  # a reader never ends a run
        return None
