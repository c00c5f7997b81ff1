"""The least time the delta rule of one prefill chunk dispatch could take on
this chip over the time it took: the larger of its bytes over the bandwidth
and its operations over the bf16 peak, over the WHOLE op time under
``attn/gdn_scan`` per jit__fused_chunk dispatch. Operations are the
recurrence's own (three [d_k, d_v] products a token and value head), not the
blocked form's, and the peak is the two-byte one although the program
multiplies float32 at ``Precision.HIGHEST`` (six passes): the share says what
a kernel could win (harness/opsbytes_gdn.gdn_chunk; rows and tokens a
dispatch from the frames' ``chunk_rows_live`` x ``chunk_c``)."""


from harness.opsbytes_gdn import gdn_chunk, least_seconds
from harness.scopes_gdn import chunk_entry_means, nested_ms, published, scan_sizes


def read(o):
    p = published(o)
    took_ms, m = nested_ms(o, "chunk", "gdn_scan"), chunk_entry_means(o)
    if not p or not took_ms or not m:
        return None
    flops, nbytes = gdn_chunk(rows=m["rows"], tokens=m["tokens"], **scan_sizes(p))
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
