"""Epoch 0 (trace, compile or cache load, resident staging, the first
val and test epochs): the first ``train`` region's beginning to the
beginning of epoch 1, where the window opens."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'setup_epoch0_s', None)
