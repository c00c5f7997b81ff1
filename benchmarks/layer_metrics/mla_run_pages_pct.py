"""Share of the latent pages a fused step's attention kernel fetched that
lay in runs of consecutive pages and came in ONE DMA a run: the program's
own count (FlightFrame ``mla_run_pages`` over ``mla_pages_read``, one layer's,
live rows only), over the window's step-only rounds. The rest came a DMA a
page: each slot's own tail past the shared document, a run that breaks inside
a group. A program without the counters (the parent of PR 38, the other
families) or whose step walks instead (the CPU backend) gives None."""


from harness.scopes_mla import step_frames


def read(o):
    fs = [f for f in step_frames(o) if getattr(f, "mla_pages_read", 0)]
    if not fs:
        return None
    return 100.0 * sum(f.mla_run_pages for f in fs) / sum(f.mla_pages_read for f in fs)
