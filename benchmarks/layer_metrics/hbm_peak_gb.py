"""Peak bytes in use on the device since process start (memory_stats)."""


def read(o):
    return o["device"]["memory_peak_bytes"] / 1e9 or None
