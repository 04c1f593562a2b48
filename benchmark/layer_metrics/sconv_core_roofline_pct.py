"""The least time the chip could take for the short convolutions' cores of
a mean train step (the larger of the operations over the bf16 peak and the
least bytes over the HBM peak: sconv_counts.py, from the held shapes and
the step records' REAL rows alone) over ``sconv_core_ms``, in percent.  The
core's time holds the recomputed forward and every intermediate the
implementation writes: time without counted work."""

import sys


def read(facts):
    try:
        lm, epochs = facts.get("lm"), facts.get("epochs") or []
        if not lm or "sconv" not in lm:
            return None
        steps = sum(e.get("steps") or 0 for e in epochs)
        rows = sum(e.get("sconv_rows") or 0 for e in epochs)
        if not steps or not rows:
            return None
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        trace_lm = (sys.modules.get("benchmark_trace_lm")
                    or run.load_module("", "trace_lm"))
        counts = (sys.modules.get("benchmark_sconv_counts")
                  or run.load_module("", "sconv_counts"))
        peaks = (sys.modules.get("benchmark_peaks")
                 or run.load_module("", "peaks"))
        s = trace_lm.scope_seconds(facts, ("sconv.core",))
        if not s:
            return None
        peak = peaks.DEVICE_PEAKS["TPU v5 lite"]
        least, _bound = counts.core_least_seconds(
            lm, rows / steps, peak["bf16_flops"], peak["hbm_bytes_per_s"])
        return 100.0 * least / s
    except Exception:  # a reader never ends a run
        return None
