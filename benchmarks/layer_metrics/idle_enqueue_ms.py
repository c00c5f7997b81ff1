"""Device idle time per scheduler round while the host was enqueueing a
dispatch (decode.enqueue.*: argument transfer and launch) or handing it to
the thread that does (a decode.dispatch.* before its enqueue starts)."""


from harness.scopes import ENQUEUE, idle_ms_per_round


def read(o):
    return idle_ms_per_round(o, ENQUEUE)
