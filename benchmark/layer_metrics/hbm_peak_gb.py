"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, read
when the window closes (before the reference runs), in 1e9 bytes."""


def read(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None
