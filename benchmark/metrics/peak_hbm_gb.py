"""Device: peak memory of the fullest chip, read by its runner when the
window closed: ``memory_stats()`` ``peak_bytes_in_use`` (live buffers) plus
``peak_bytes_reserved`` (the programs' temporaries)."""


def read(w):
    peak = w.device["memory_peak_bytes"]
    return peak / 1e9 if peak else None
