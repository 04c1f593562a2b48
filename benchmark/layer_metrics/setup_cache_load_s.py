"""Seconds of set-up spent reading executables from the persistent compile
cache: the sum of ``cache_load_s`` over the ``program`` records before the
window whose ``cache`` says ``hit``."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    records = (sys.modules.get("benchmark_program_records")
               or run.load_module("", "program_records"))
    return records.read(facts, 'setup_cache_load_s')
