"""Share of device-busy time spent in Mosaic (Pallas) custom calls, found
by what the trace says an op is, not by a kernel's name."""


def read(facts):
    tr = facts.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy_s"]
