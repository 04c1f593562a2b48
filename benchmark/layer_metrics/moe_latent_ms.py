"""The latent expert layers' routed part (scopes ``moe.route`` +
``moe.latent`` + ``moe.experts``: the router over the hidden state, both
projections of the latent space, and the held experts' rows, grouped
products and sums in it), forward, recomputed forward and backward,
milliseconds of a train step summed over the expert layers (trace_lm.py).
None over a program without a ``moe.latent`` scope (a stack whose experts
read the hidden state)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        if lm.scope_ms(facts, ("moe.latent",)) is None:
            return None
        return lm.scope_ms(facts, ("moe.route", "moe.latent", "moe.experts"))
    except Exception:  # a reader never ends a run
        return None
