"""FLOPs of the grouped products over the slots really routed to a held
expert (lm_counts.py) over the grouped products' time (``moe.gmm``,
forward and backward), as a share of the chip's bf16 peak."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.grouped_mxu_pct(facts)
    except Exception:  # a reader never ends a run
        return None
