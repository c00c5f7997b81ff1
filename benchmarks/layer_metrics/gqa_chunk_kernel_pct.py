"""Share of the window's prefilling chunk rows whose grouped-query attention
ran in the chunk kernel (ops/gqa_decode.py ``gqa_chunk_attention``: scores,
probabilities and the softmax state in VMEM, the K/V pages of both planes
read where they lie) and not in the page gather: the program's own count
(FlightFrame ``chunk_rows_kernel`` over ``chunk_rows_live``), summed over the
window's rounds that ran a chunk. The arithmetic of ``mla_chunk_kernel_pct``,
for the sparse-expert, hybrid and short-convolution families' cells. Which
path a chunk program takes is static (the pool's planes, the platform, the
entry's shape and the head group), so a cell reads 100 or 0: the guard that a
later change did not drop a cell back to the gather unseen. 0 where every
chunk gathers (the parent of PR 52, whose families had no chunk kernel to
count); a program without the counter gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "chunk_rows_live", 0)]
    if not fs or not all(hasattr(f, "chunk_rows_kernel") for f in fs):
        return None
    return 100.0 * sum(f.chunk_rows_kernel for f in fs) / sum(f.chunk_rows_live for f in fs)
