"""Share of the node slots the train steps of the counted epochs computed
on that held no real node: the telemetry step records' ``padding`` block
(real nodes from the in-jit mask sum, padded nodes from the batch shape),
as the language-model driver sums them.  A driver that does not sum them
(the stock one) leaves nothing to read."""


def read(facts):
    epochs = facts.get("epochs") or []
    real = sum(e.get("nodes_real") or 0 for e in epochs)
    padded = sum(e.get("nodes_padded") or 0 for e in epochs)
    return 100.0 * (1.0 - real / padded) if padded else None
