"""The least time the stream traffic of one fused step could take on this chip
over the time the multi-stream residual path took: max(ops / peak FLOP/s, bytes
/ peak bytes/s) over ``mhc_device_ms``. Bytes: around each of the 2 x layers
blocks the [rows, 4, C] state read once and written once, ``u`` written, ``o``
read, for the rows that the program's frames say generated (``moe_rows``);
the maps' weights are not counted (harness/opsbytes_mhc.mhc_mix,
harness/peaks.py). 12 FLOP a byte against the chip's 240: the bytes bind; what
holds the operator is the latency of its small dependent ops, so this reads low."""


from harness.opsbytes_mhc import least_seconds, mhc_mix
from harness.scopes_mhc import blocks, nested_ms, streams
from harness.scopes_mla import step_means


def read(o):
    took_ms, m = nested_ms(o, "step"), step_means(o)
    if not took_ms or not m:
        return None
    flops, nbytes = mhc_mix(rows=m["rows"], streams=streams(o), hidden=o["geometry"]["hidden"], blocks=blocks(o))
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
