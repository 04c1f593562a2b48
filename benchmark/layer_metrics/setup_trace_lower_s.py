"""Host seconds of set-up spent tracing Python into jaxprs and lowering them
to StableHLO: the sum of ``trace_s`` + ``lower_s`` over the program's own
``program`` records (one per program JAX builds) that ended before the
window opened.  A warm compile cache does not shorten it."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'setup_trace_lower_s')
