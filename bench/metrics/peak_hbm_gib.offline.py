"""Peak device memory in use after the window, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
