"""What the compiler reserves for the train step's temporaries on one chip:
the largest ``temp_bytes`` among the ``program_memory`` records (the
executable's ``memory_analysis()``) of the programs that
``facts["train_module_regex"]`` names.  ``memory_stats()``, which
``hbm_peak_gb`` reads, counts arrays and not these.  Nothing on a backend
that reports no memory (the CPU)."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'hbm_step_temp_gb')
