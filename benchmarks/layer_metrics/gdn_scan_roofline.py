"""The least time the delta rule of one fused step could take on this chip
over the time it took: max(ops / peak FLOP/s, bytes / peak bytes/s) over the
WHOLE op time under ``attn/gdn_scan``. Bytes are the float32 matrix state of
the rows that the program's frames say generated (the step's own
``ssm_rows``) read once and written once, their conv cache written, q, k and
v read (harness/opsbytes_gdn.gdn_scan, harness/peaks.py). The bytes bind: 7
operations a state element against 8 bytes. The program reads the state twice
(no kernel holds a head's matrix in VMEM yet): the share says so."""


from harness.opsbytes_gdn import gdn_scan, least_seconds
from harness.scopes_gdn import nested_ms, published, scan_sizes, step_means


def read(o):
    p = published(o)
    took_ms, m = nested_ms(o, "step", "gdn_scan"), step_means(o)
    if not p or not took_ms or not m:
        return None
    flops, nbytes = gdn_scan(rows=m["rows"], **scan_sizes(p))
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
