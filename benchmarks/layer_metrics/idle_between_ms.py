"""Device idle time per scheduler round outside every decode.dispatch.* span
(the round's phases, stream flushes, the loop's other tasks): what is left of
the idle that ``idle_by_state`` counts after ``idle_launch_ms`` and
``idle_return_ms`` (harness/dispatches.py). None on a program whose dispatch
annotations carry no ``seq`` (the parent of PR 39)."""


from harness.dispatches import leg_ms_per_round


def read(o):
    return leg_ms_per_round(o, "between")
