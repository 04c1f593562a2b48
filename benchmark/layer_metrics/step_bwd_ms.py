"""Backward pass, milliseconds of a train step: the ops whose scope is
``step.loss/transpose(jvp(...))``; computed like ``step_fwd_ms``."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'phase_ms', ('bwd',))
