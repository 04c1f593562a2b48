"""Latent attention's core (scores, softmax, values over each graph's
nodes: scope ``mla.core``, the attention kernels and the pads round them),
forward, recomputed forward and backward, milliseconds of a train step
summed over the attending layers (trace_lm.py)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("mla.core",))
    except Exception:  # a reader never ends a run
        return None
