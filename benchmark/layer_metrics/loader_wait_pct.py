"""Share of the counted epochs' wall time the trainer thread spent inside
the train loader's ``next()`` (collate, stacking, host->device staging of a
resident epoch 0 excluded: counted epochs only).  Host clock, read by the
driver's stopwatch round the loader.  Not the share of the ``train``
region: that region times dispatch, and with the loader synchronous it is
nearly all ``next()`` whatever the device does."""


def read(facts):
    epochs = facts["epochs"]
    if not epochs:
        return None
    t0, t1 = epochs[0]["t0"], epochs[-1]["t1"]   # counted epochs adjoin
    waited = sum(w for t, w in facts["loader_waits"] if t0 <= t <= t1)
    return 100.0 * waited / (t1 - t0)
