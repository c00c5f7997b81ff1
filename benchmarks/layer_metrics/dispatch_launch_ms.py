"""The host's LAUNCH leg of a round's dispatches, from durations alone: over
the dispatches of the traced slice's whole rounds, a dispatch's wall (its
``decode.dispatch.*`` span) less its return part (its ``decode.copyout.*``
start to the span's end; both the host's clock) less the device time of its
modules (the device's clock), summed, a round. The modules are joined to
dispatches BY ORDER (harness/ready.py), so the offset between the two planes'
clocks, another in each profiler session, does not enter: compare it across
sessions where ``idle_launch_ms`` moves by a millisecond or two. None on a
program without ``decode.copyout.*`` (the parent of PR 53)."""


from harness.ready import launch_ms_per_round, say


def read(o):
    say(o)
    return launch_ms_per_round(o)
