"""Operations and bytes of the multi-stream residual path (``ops/mhc.py``),
from shapes. Companion of ``opsbytes_mla.py`` for the ``xing4.0-29b-a4b``
configuration.

``mhc_mix`` returns ``(flops, bytes)`` for ONE dispatch. Bytes are the least
the mathematics needs: around each block the ``[rows, n, C]`` state is read
once and written once, ``u`` (what the block reads) written and ``o`` (what it
gave) read. The maps' ``phi`` (0.69 MB a block at the published widths) is NOT
counted: the chip's compiler prefetches small weights under other scopes'
time (PERF.md section 7, PR 42 (a)), and a share must not pass 100. Counted
that low, a share of the roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def state_bytes(*, rows, streams, hidden, blocks, act_bytes=2):
    """One pass of every block over the state: ``rows x n x C`` numbers a block."""
    return float(blocks * rows * streams * hidden * act_bytes)


def mhc_mix(*, rows, streams, hidden, blocks, act_bytes=2):
    """A row and block cost: the sum of squares (2 nC), the three products
    (2 nC (2n + n^2)), the pre-mix (2 nC), the post / stream mix (2 (n^2 + n)
    C); the Sinkhorn iterations are n^2 numbers a row and are left out."""
    n, c = streams, hidden
    flops = 2.0 * rows * blocks * (n * c * (2 * n + n * n) + 2 * n * c + (n * n + n) * c)
    nbytes = blocks * rows * (2 * n * c + 2 * c) * act_bytes
    return float(flops), float(nbytes)
