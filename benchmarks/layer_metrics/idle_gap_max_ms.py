"""The device's longest single idle interval in the span the traced slice's
rounds cover (the complement of the union of its op events, as
``device_idle_pct.gen`` takes them). A slice that holds a stall says so: one
gap of a second is 25 points of ``device_idle_pct.gen`` that no round's
arithmetic explains. The run's earlier line ``{"phase": "ready"}`` lists
every gap over 20 ms with the dispatches on either side of it, whether the
earlier one had marked its result ready, the program's own ``decode.*`` spans
that cover it (``idle_wait``, a phase, ``gc2``) and the frame's ``active`` /
``queued`` (harness/ready.py). None on the parent of PR 53."""


from harness.ready import gap_max_ms, say


def read(o):
    say(o)
    return gap_max_ms(o)
