"""Share of the rows its prefill chunk dispatches computed that were slots
prefilling: the program's own count (FlightFrame ``chunk_rows_live`` over
``chunk_rows``), summed over the window's rounds that ran a chunk. 100 / 16
= 6.25 per live slot where every chunk program computes all 16 slots; 50
where one slot prefills in a two-row batch. A program without the counters
(the parent of PR 33) gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "chunk_rows", 0)]
    if not fs:
        return None
    return 100.0 * sum(f.chunk_rows_live for f in fs) / sum(f.chunk_rows for f in fs)
