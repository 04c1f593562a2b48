"""Share of the device's busy time in the window spent in ops under
``step.eval`` (the val and test epochs' forward), mean over chips.  The
window straddles an epoch boundary, so it holds about an epoch's worth
of each."""

import sys


def read(facts):
    run = sys.modules.get("benchmark_run") or sys.modules["__main__"]
    scopes = (sys.modules.get("benchmark_trace_scopes")
              or run.load_module("", "trace_scopes"))
    return scopes.read(facts, 'eval_share_pct', None)
