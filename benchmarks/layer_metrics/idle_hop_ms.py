"""The part of ``idle_launch_ms`` + ``idle_return_ms`` in which no thread was
in the runtime: per round, device idle inside a dispatch span before any
thread entered its decode.enqueue.* and after its last decode.enqueue.* /
decode.readback.* ended (the two thread hops of ``_device_call``: asyncio's
and the GIL's time). A sub-account, not a fourth leg (harness/dispatches.py).
None on a program whose dispatch annotations carry no ``seq``."""


from harness.dispatches import leg_ms_per_round


def read(o):
    return leg_ms_per_round(o, "hop")
