"""Share of the window's chunk wall that was NOT the usual entry's: FlightFrame
``busy_ns[chunk]`` (host call to readback return) of the chunk rounds whose
``(chunk_rows, chunk_c)`` is not the window's most frequent entry, over all
chunk rounds' (harness/dispatches.py ``frame_entries``; the entries are on
the run's earlier line ``{"phase": "dispatches"}``). None on a program whose
frames carry no ``chunk_c`` (the parent of PR 39)."""


from harness.dispatches import offentry_wall_pct, say


def read(o):
    say(o)
    return offentry_wall_pct(o)
