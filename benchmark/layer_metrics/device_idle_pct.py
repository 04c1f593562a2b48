"""1 - union of device-op intervals over the traced window, averaged over
the chips used.  The window straddles one epoch boundary (val / test /
fetch tail and the next epoch's first train dispatches)."""


def read(facts):
    tr = facts.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
