"""How late the open-loop generator sent, worst request of the window: a
starved generator must not be read as a fast server."""


def read(o):
    return o["client_summary"]["generator_late_ms_max"]
