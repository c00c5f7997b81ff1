"""The host's RETURN leg of a round's dispatches, as the program itself times
it: FlightFrame ``rdy_ns`` (per family, the part of the blocked readback
after the read learned that the result was ready: the copy to the host, the
return through the executor to the loop, up to the dispatch's end) summed
over families and over the window's frames, a round. A host-clock duration:
no trace and no second clock. In a traced run the frames after the profiler's
slice are left out (they run 2 ms a dispatch slower: PERF.md section 7,
PR 39 (b)); the run's earlier line ``{"phase": "ready"}`` has the window's
three parts (harness/ready.py). None on a program whose frames lack the slot
(the parent of PR 53)."""


from harness.ready import return_ms_per_round, say


def read(o):
    say(o)
    return return_ms_per_round(o)
