"""Forward pass, milliseconds of a train step: the self time of the ops
whose scope is ``step.loss/jvp(...)`` (no ``transpose(`` below it) inside
the train-step programs, as a share of their self time, times the accepted
``step_device_ms``.  Scopes: hlo_scopes.json, joined in trace_scopes.py."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'phase_ms', ('fwd',))
