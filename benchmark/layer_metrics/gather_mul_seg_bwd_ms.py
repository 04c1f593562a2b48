"""The ``gather_mul_seg_bwd`` Mosaic kernel (the same kernel on the
sender-sorted order: dx of the CFConv aggregation), milliseconds of a
train step, the conv layers summed; computed like
``gather_mul_seg_fwd_ms``."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'kernel_ms', ('gather_mul_seg_bwd',))
