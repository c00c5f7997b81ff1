"""Share of the window's delta-rule layer passes that ran in the kernels
(ops/gated_delta.py ``gdn_step_rows`` / ``gdn_chunk_rows``: a value head's
matrix state through VMEM once a dispatch, the state rows read and written
where they lie) and not in the plain ``jax.numpy`` forms: the program's own
count (FlightFrame ``gdn_kernel_passes`` over ``gdn_passes``), summed over the
window's rounds. Which form a program takes is static (the platform, the
head's lane tiles, the state rows' type) and counted from what the program's
trace decided, a step and a chunk dispatch apart, so a cell reads 100, 0, or
in between where one kind of dispatch fell back: the guard that a later
change did not drop the cell back to the plain forms unseen.
None for a program without the counter (the parent of PR 58) and for a
configuration without delta-rule layers."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "gdn_passes", 0)]
    if not fs:
        return None
    return 100.0 * sum(f.gdn_kernel_passes for f in fs) / sum(f.gdn_passes for f in fs)
