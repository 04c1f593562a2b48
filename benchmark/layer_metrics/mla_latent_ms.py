"""Latent attention's own products round the core (scopes ``mla.down`` +
``mla.up`` + ``mla.out``: the query and key/value bottlenecks and their
norms, the up-projections, rotary and the shared rotary key's assembly, the
output product), forward, recomputed forward and backward, milliseconds of
a train step summed over the attending layers (trace_lm.py)."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("mla.down", "mla.up", "mla.out"))
    except Exception:  # a reader never ends a run
        return None
