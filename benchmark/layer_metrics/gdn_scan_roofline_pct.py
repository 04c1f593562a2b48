"""The least time the chip could take for the gated delta rule of a mean
train step (the larger of the published chunked form's operations over the
bf16 peak and the least bytes over the HBM peak: gdn_counts.py, from the
held shapes and the step records' REAL chunks alone) over ``gdn_scan_ms``,
in percent.  The rule's time holds the recomputed forward, the decays, the
inverse's rounds and every intermediate the implementation writes: time
without counted work."""

import sys


def read(facts):
    try:
        lm, epochs = facts.get("lm"), facts.get("epochs") or []
        if not lm or "gdn" not in lm:
            return None
        steps = sum(e.get("steps") or 0 for e in epochs)
        real = sum((e.get("gdn_chunks") or 0)
                   - (e.get("gdn_chunks_padding") or 0) for e in epochs)
        if not steps or not real:
            return None
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        trace_lm = (sys.modules.get("benchmark_trace_lm")
                    or run.load_module("", "trace_lm"))
        counts = (sys.modules.get("benchmark_gdn_counts")
                  or run.load_module("", "gdn_counts"))
        peaks = (sys.modules.get("benchmark_peaks")
                 or run.load_module("", "peaks"))
        s = trace_lm.scope_seconds(facts, ("gdn.scan",))
        if not s:
            return None
        peak = peaks.DEVICE_PEAKS["TPU v5 lite"]
        least, _bound = counts.rule_least_seconds(
            lm, real / steps, peak["bf16_flops"], peak["hbm_bytes_per_s"])
        return 100.0 * least / s
    except Exception:  # a reader never ends a run
        return None
