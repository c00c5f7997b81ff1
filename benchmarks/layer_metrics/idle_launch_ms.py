"""Device idle time per scheduler round inside a dispatch before its device
work is under way (a decode.dispatch.* span up to the start of its modules:
hand-off to the executor, the jitted call, argument transfer, the runtime's
launch). Split at the DEVICE's module events, not at where the host's
enqueue annotation ends (harness/dispatches.py). None on a program whose
dispatch annotations carry no ``seq`` (the parent of PR 39)."""


from harness.dispatches import leg_ms_per_round


def read(o):
    return leg_ms_per_round(o, "launch")
