"""Host data work before the window opens: the sum of the ``data.collate``,
``data.stack`` and ``data.h2d`` regions that began before epoch 1."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'setup_region_s', ('data.collate', 'data.stack', 'data.h2d'))
