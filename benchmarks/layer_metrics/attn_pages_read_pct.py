"""Share of the pages its block tables name that a fused step's attention
read: the program's own count (FlightFrame ``attn_pages_read`` over
``attn_pages_table``, on the host from the round's positions), over the
window's rounds that ran a plain step. 100 where the step gathers every
slot's whole table; under it where the paged-attention kernel stops at each
slot's length: a free slot reads one page of its 44. A program without the
counters (the parent of PR 29) gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "attn_pages_table", 0)]
    if not fs:
        return None
    return 100.0 * sum(f.attn_pages_read for f in fs) / sum(f.attn_pages_table for f in fs)
