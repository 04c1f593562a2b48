"""The gated delta rule alone (scope ``gdn.scan``, ops/gdn.py and the lines
of models/qwen3_next.py round its call: the l2 norms of q and k, ``g`` and
``beta``, the chunked form's products, decays and unit lower-triangular
inverse, the walk over chunk states), forward, recomputed forward and
backward, milliseconds of a train step summed over the DeltaNet layers
(trace_lm.py).  None over a program without the scope."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("gdn.scan",))
    except Exception:  # a reader never ends a run
        return None
