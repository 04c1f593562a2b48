"""The router alone (scope ``moe.route``: the float32 product at HIGHEST
precision, the scores, ``top_k``, the selected scores' read and their
renormalisation, the held mask and the loads), forward, whatever of it a
checkpoint recomputes and backward, milliseconds of a train step summed
over the expert layers (trace_lm.py).  Inside ``moe_routed_ms`` and
``moe_latent_ms``."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("moe.route",))
    except Exception:  # a reader never ends a run
        return None
