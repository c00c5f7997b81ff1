"""Share of the K/V pages a fused step's grouped-query attention kernel
fetched that lay in runs of consecutive pages and came in ONE DMA a run: the
program's own count (FlightFrame ``attn_run_pages``, one layer's a page kind,
from ``ops/gqa_decode.py`` ``pages_fetched``) over what the step read
(``attn_pages_read``, the scheduler's count from the round's positions), over
the window's rounds that ran a plain step. 0 where the step gathers (the
kernel did not engage: the CPU backend, a pool Mosaic cannot tile, the parent
of the PR that gave a family the kernel); the rest of what the kernel read
came a DMA a page. A program that counts neither (the parent of PR 42) gives
None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "attn_pages_read", 0)]
    if not fs or not all(hasattr(f, "attn_run_pages") for f in fs):
        return None
    return 100.0 * sum(f.attn_run_pages for f in fs) / sum(f.attn_pages_read for f in fs)
