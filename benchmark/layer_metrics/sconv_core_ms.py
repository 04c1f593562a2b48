"""The short convolution's core alone (scope ``sconv.core``, ops/sconv.py:
the gate B * X, the depthwise causal taps that stop at graph boundaries,
the gate C * v), forward, recomputed forward and backward, milliseconds of
a train step summed over the conv layers (trace_lm.py).  None over a
program without the scope."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("sconv.core",))
    except Exception:  # a reader never ends a run
        return None
