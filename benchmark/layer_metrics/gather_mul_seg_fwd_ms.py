"""The ``gather_mul_seg_fwd`` Mosaic kernel (ops/fused_mp.py: gather
x[send] * w -> sorted segment sum; SchNet's CFConv aggregation at 128
filters, under the 256 of the whole-pipeline ``scf_*`` kernels),
milliseconds of a train step, the conv layers summed: self time of the
custom calls so named inside the train-step programs, scaled like
``step_fwd_ms``."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'kernel_ms', ('gather_mul_seg_fwd',))
