"""Share of the slots with a prefill chunk to run that a round left for a
later one, because the chunk ladder's widest entry holds fewer: the program's
own count (FlightFrame ``chunk_rows_held`` over ``chunk_rows_live`` +
``chunk_rows_held``), summed over the window's rounds that ran a chunk. 0
where no round of the window had more slots prefilling than its widest entry
holds. A program without the counter (the parent of PR 44, whose rounds took
every prefilling slot at full width) gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "chunk_rows", 0)]
    if not fs or not all(hasattr(f, "chunk_rows_held") for f in fs):
        return None
    held = sum(f.chunk_rows_held for f in fs)
    return 100.0 * held / (held + sum(f.chunk_rows_live for f in fs))
