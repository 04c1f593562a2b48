"""FLOPs of the VISIBLE (i, j) pairs of a mean train step at 20 heads,
keys 256 and values 256 wide, over every attending layer (from the train
documents' lengths: mla_counts.py) over ``mla_core_ms``, as a share of the
chip's bf16 peak.  What the kernels compute beyond the visible pairs
(masked blocks of the 4096 band, padding nodes, the recomputed forward) is
time without operations."""

import sys


def read(facts):
    try:
        lm = facts.get("lm")
        if not lm or "mla" not in lm:
            return None
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        trace_lm = (sys.modules.get("benchmark_trace_lm")
                    or run.load_module("", "trace_lm"))
        counts = (sys.modules.get("benchmark_mla_counts")
                  or run.load_module("", "mla_counts"))
        s = trace_lm.scope_seconds(facts, ("mla.core",))
        if not s:
            return None
        return (100.0 * counts.mla_core_flops_per_step(lm) / s
                / trace_lm._PEAK_BF16)
    except Exception:  # a reader never ends a run
        return None
