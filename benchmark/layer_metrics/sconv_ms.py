"""The short-convolution operators (scopes ``sconv.in`` + ``sconv.core`` +
``sconv.out``: the norm and the input product to [B | C | X], the two gates
round the boundary-aware taps, the output product), forward, recomputed
forward and backward, milliseconds of a train step summed over the conv
layers (trace_lm.py).  None over a program without these scopes."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("sconv.in", "sconv.core", "sconv.out"))
    except Exception:  # a reader never ends a run
        return None
