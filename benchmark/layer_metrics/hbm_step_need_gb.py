"""What must be free on one chip for the train step to run, by the compiler's
own statement: argument + output - alias + temp + generated code bytes of
the train-step program with the most temporaries (its ``program_memory``
record).  The state at rest is among the arguments; the donated state's
outputs alias it.  Nothing on a backend that reports no memory (the
CPU)."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'hbm_step_need_gb')
