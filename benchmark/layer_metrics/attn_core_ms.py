"""The attention kernels (scores, softmax, values; ops/attention.py under
``attn.core``), forward, recomputed forward and backward, milliseconds
of a train step summed over the layers (trace_lm.py)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("attn.core",))
    except Exception:  # a reader never ends a run
        return None
