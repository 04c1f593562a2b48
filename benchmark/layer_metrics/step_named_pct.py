"""Share of the train step's self time whose scope resolves to a declared
phase (``step.*`` or ``comm.*``), an operand's scope counting for the
copies and slices the compiler inserts.  What is left has no name a
``perf_opt`` issue could start from."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'named_pct', None)
