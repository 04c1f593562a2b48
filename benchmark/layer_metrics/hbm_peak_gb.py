"""HBM held at the fullest on the fullest chip after the window: the larger
of the allocator's ``peak_bytes_in_use`` (live buffers) and
``peak_bytes_reserved`` (what loaded programs reserve for their
temporaries) — a lower bound of the true peak, the same figure the result
line gives as ``memory_peak_bytes``.  A backend that reports none (the CPU)
gives nothing to read."""


def read(facts):
    peak = facts["memory_peak_bytes"]
    return peak / 1e9 if peak else None
