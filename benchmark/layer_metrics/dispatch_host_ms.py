"""Host time of one train step call: the mean ``train.dispatch`` region
(argument ingest and enqueue; it does not wait for the device, though it
blocks while the runtime's queue is full) over the counted epochs, from
the driver's ``facts["spans"]``."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'region_ms', ('train.dispatch',))
