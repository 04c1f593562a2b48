"""Share of the edge slots the train steps of the counted epochs computed
on that held no real edge.  Exact count: the telemetry step records'
``padding`` block (real edges from the in-jit mask sum, padded edges from
the batch shape)."""


def read(facts):
    real = sum(e["edges_real"] for e in facts["epochs"])
    padded = sum(e["edges_padded"] for e in facts["epochs"])
    return 100.0 * (1.0 - real / padded) if padded else None
