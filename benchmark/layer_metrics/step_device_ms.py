"""Device-busy time inside the traced executions of the train-step program
divided by the optimizer steps in them (executions x scan K), from the
profiler trace alone."""


def read(facts):
    tr = facts.get("trace")
    if not tr or tr["step_device_s"] is None:
        return None
    return 1e3 * tr["step_device_s"]
