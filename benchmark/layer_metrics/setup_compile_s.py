"""Seconds of set-up that XLA spent compiling: the sum of ``build_s`` less
``cache_load_s`` over the ``program`` records before the window whose
``cache`` says ``miss`` (the persistent cache was asked and had none) or
``off`` (it was not asked).  Near 0 on a warm run; on a cold one about the
cold ``setup_s`` less the warm one."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'setup_compile_s')
