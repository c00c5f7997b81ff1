"""The least time the gated short convolutions of one fused step could take
on this chip over the time they took: max(ops / peak FLOP/s, bytes / peak
bytes/s) over the op time under the three ``conv_*`` scopes. Bytes are each
conv layer's projections and taps read once (33.6 MB a layer at the published
widths) and the cached inputs of the rows that the program's frames say
advanced (``conv_rows``) read and written in float32; FLOPs 2 x 4 x hidden^2
a row and layer (harness/opsbytes_conv.conv_mix, harness/peaks.py). At 64
rows, 128 FLOP a weight byte against the chip's 240: the bytes bind."""


from harness.opsbytes_conv import conv_mix, least_seconds
from harness.scopes_conv import CONV, nested_ms, published, step_means


def read(o):
    took_ms, m = nested_ms(o, "step", *CONV), step_means(o)
    if not took_ms or not m:
        return None
    p = published(o)
    flops, nbytes = conv_mix(hidden=p["hidden"], conv_layers=p["layers"] - p["attn_layers"], taps=p["taps"], rows=m["rows"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
