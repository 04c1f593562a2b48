"""FLOPs of the VISIBLE (i, j) pairs of a mean train step (from the
train documents' lengths, the window and the heads held: lm_counts.py) over
``attn_core_ms``, as a share of the chip's bf16 peak.  What the kernels
compute beyond the visible pairs (masked blocks of the band, padding
nodes, the recomputed forward) is time without operations."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.attention_core_mxu_pct(facts)
    except Exception:  # a reader never ends a run
        return None
