"""Device idle time per scheduler round while the host was in a dispatch's
blocking read (decode.readback.*: waiting for the device, then device to
host) or between its end and the loop running again (the rest of the
decode.dispatch.*: the hand-off back)."""


from harness.scopes import READBACK, idle_ms_per_round


def read(o):
    return idle_ms_per_round(o, READBACK)
