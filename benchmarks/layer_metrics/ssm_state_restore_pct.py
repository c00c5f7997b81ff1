"""Share of the window's admissions that began from a cached prefix's state
snapshot: the program's own counts (FlightFrame ``state_restores`` over
``admitted``). 100 where every request rides the shared prefix; less, and
the state path of the prefix cache fell back to cold prefill (an entry
evicted, a snapshot never taken). None for a program without a state cache."""


from harness.scopes_ssm import restore_share


def read(o):
    share = restore_share(o)
    return None if share is None else 100.0 * share
