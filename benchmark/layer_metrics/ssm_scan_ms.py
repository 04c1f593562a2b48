"""The selective scan alone (scope ``ssm.scan``, ops/ssm.py: the chunked
form's products and decays, the scan over chunk states, the D skip),
forward, recomputed forward and backward, milliseconds of a train step
summed over the state-space layers (trace_lm.py).  None over a program
without the scope."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("ssm.scan",))
    except Exception:  # a reader never ends a run
        return None
