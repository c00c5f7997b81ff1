"""A streamed request's time on the scheduler's event loop from its body's
bytes in hand to its place in the queue: the body's JSON parse, the message,
the task hops to ``DecodeScheduler.submit`` (FlightFrame ``ingress_ns`` over
``ingress_requests``, the window's frames; telemetry/flight.py ``Ingress``).
None on a program without the fields (the parent of PR 39)."""


from harness.dispatches import ingress_ms


def read(o):
    return ingress_ms(o)
