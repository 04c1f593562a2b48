"""The Gated DeltaNet layers (scopes ``gdn.in`` + ``gdn.conv`` + ``gdn.scan``
+ ``gdn.norm`` + ``gdn.out``: the norm and both input products, the
boundary-aware convolution, the gated delta rule, the gated norm and the
output product), forward, what a checkpoint recomputes and backward,
milliseconds of a train step summed over the layers (trace_lm.py).  None
over a program without these scopes."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("gdn.in", "gdn.conv", "gdn.scan",
                                   "gdn.norm", "gdn.out"))
    except Exception:  # a reader never ends a run
        return None
