"""Imbalance over ALL the router's experts, held or not: the fullest one's
slots over the mean, averaged over the expert layers and the steps of the
counted epochs (the step records' ``moe.load_all_max_over_mean``, which
only a router under a correction bias reports: it is what the bias acts
on).  1.0 is a flat router."""


def read(facts):
    vals = [e.get("moe_load_all_max_over_mean")
            for e in facts.get("epochs") or []]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
