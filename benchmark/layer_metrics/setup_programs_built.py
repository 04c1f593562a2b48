"""How many programs JAX built before the window opened: the count of the
program's own ``program`` records that ended by then, the small eagerly
dispatched ones (``convert_element_type``, ``broadcast_in_dim``) with the
step programs.  It counts build spans, so it is a ``program_span`` metric
and a CPU rehearsal leaves it out."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'setup_programs_built')
