"""Peak of live device buffers alone on the fullest chip
(``peak_bytes_in_use``): parameters, optimizer state, batches, a resident
corpus, activations XLA does not keep in a program's own reservation.
``hbm_peak_gb`` also counts what loaded programs reserve."""


def read(facts):
    peak = facts["memory_live_peak_bytes"]
    return peak / 1e9 if peak else None
