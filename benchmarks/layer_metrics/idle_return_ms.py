"""Device idle time per scheduler round inside a dispatch after its last
module ended (device to host, the blocked read's return, the hop back to the
loop), split at the DEVICE's module events (harness/dispatches.py). None on
a program whose dispatch annotations carry no ``seq`` (the parent of PR 39)."""


from harness.dispatches import leg_ms_per_round


def read(o):
    return leg_ms_per_round(o, "return")
