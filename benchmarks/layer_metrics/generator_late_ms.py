"""How late the open-loop generator sent: 95th percentile over the window's
requests of send time less due time. A generator that cannot keep up sends
late across the board, and must not be read as a slow server. The WORST
request is not read here since PR 31: the chip's host stands still for
~105 ms two to four times a window, every process at once (the load child's
idle timer wakes as late, the server's round is as long), and with hundreds
of requests one is nearly always due inside such a stall. The client line
of a run has the worst, the count over 5 ms and the stalls beside them."""


def read(o):
    return o["client_summary"]["generator_late_ms_p95"]
