"""Share of the window's prefilling chunk rows whose latent attention ran in
the chunk kernel (ops/mla.py ``mla_chunk_attention``: scores, probabilities
and the softmax state in VMEM, the latent pages read where they lie) and not
in the blocked ``jnp`` walk: the program's own count (FlightFrame
``chunk_rows_kernel`` over ``chunk_rows_live``), summed over the window's
rounds that ran a chunk. Which path a chunk program takes is static (the
pool's kind, the platform, the entry's shape), so a cell reads 100 or 0: the
guard that a later change did not drop a latent cell back to the walk unseen.
A program without the counter (the parent of PR 45, whose chunks all walk)
gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "chunk_rows_live", 0)]
    if not fs or not all(hasattr(f, "chunk_rows_kernel") for f in fs):
        return None
    return 100.0 * sum(f.chunk_rows_kernel for f in fs) / sum(f.chunk_rows_live for f in fs)
