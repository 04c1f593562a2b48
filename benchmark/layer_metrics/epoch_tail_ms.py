"""Host-serial work at an epoch's end during which the device has nothing
queued: ``telemetry.flush`` (fetch and write the step records) plus
``epoch.tail`` (scheduler, history, checkpoint, JSONL, prints,
``set_epoch``), milliseconds per counted epoch."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'region_ms_per_epoch', ('epoch.tail', 'telemetry.flush'))
