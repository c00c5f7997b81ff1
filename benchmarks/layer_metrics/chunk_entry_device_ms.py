"""Device time of one prefill chunk dispatch of ONE ``chunk_buckets`` entry:
the mean over the traced slice's whole chunk dispatches of its most frequent
``(rows, c)``, each dispatch's jit__fused_chunk modules joined to its
decode.dispatch.chunk annotation's ``rows`` / ``c`` stats
(harness/dispatches.py). ``chunk_device_ms`` is the mean over whatever
entries the slice held. The entry, and every other beside it, is on the run's
earlier line ``{"phase": "dispatches"}``. None on a program without the
stats (the parent of PR 39)."""


from harness.dispatches import chunk_entry_ms, say


def read(o):
    say(o)
    return chunk_entry_ms(o)
