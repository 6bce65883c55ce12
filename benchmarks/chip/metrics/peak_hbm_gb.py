"""Peak device memory in use (``peak_bytes_in_use`` after the window,
before anything else runs), in GB of 1e9 bytes."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
