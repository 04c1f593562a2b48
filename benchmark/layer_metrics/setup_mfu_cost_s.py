"""The second compile of the train step that the in-run MFU estimate
makes for XLA's cost model (telemetry/logger.py, one per bucket shape,
at epoch 0's flush): the ``setup.mfu_cost`` regions before epoch 1."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'setup_region_s', ('setup.mfu_cost',))
