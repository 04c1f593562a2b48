"""Share of the traced window on device 0 in which a collective runs and
no other op does.  Nothing to read where no collective ran (one chip)."""


def read(facts):
    tr = facts.get("trace")
    if not tr or not tr["window_s"] or not tr["collective_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
