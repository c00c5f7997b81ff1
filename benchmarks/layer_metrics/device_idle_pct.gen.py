"""Share of the traced slice in which no operation ran on the device:
1 - union of device op intervals over the slice (harness/trace.py)."""


def read(o):
    t = o["trace"]
    return None if not t or t["idle_share"] is None else 100.0 * t["idle_share"]
