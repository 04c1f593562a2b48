"""The multi-token-prediction module (scopes ``mtp.proj`` + ``mtp.layer`` +
``mtp.head``: the next id's embedding and eh_proj, its own expert layer
with that layer's latent attention and routed experts, its final norm and
the main head's product), forward, recomputed forward and backward,
milliseconds of a train step (trace_lm.py).  Its loss is the trainer's
(``lm.xent``) and is not in it."""

import sys


def read(facts):
    try:
        run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
        lm = (sys.modules.get("benchmark_trace_lm")
              or run.load_module("", "trace_lm"))
        return lm.scope_ms(facts, ("mtp.proj", "mtp.layer", "mtp.head"))
    except Exception:  # a reader never ends a run
        return None
