"""The device's peak memory in use, in GB (1e9 bytes), as the runtime's
allocator counts it (``peak_bytes_in_use`` of the fullest chip), read at
the end of the measured window, before the reference runs. The checked
round in set-up holds the program's largest state, so set-up sets it."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
