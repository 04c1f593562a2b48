"""Imbalance over the held experts: the fullest one's load over the mean,
averaged over the expert layers and the steps of the counted epochs (the
step records' ``moe`` block, ops/moe.py stats).  1.0 is a flat router."""


def read(facts):
    vals = [e.get("moe_load_max_over_mean")
            for e in facts.get("epochs") or []]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
