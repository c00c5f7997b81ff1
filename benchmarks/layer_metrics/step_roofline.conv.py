"""The least time one fused decode step of the short-convolution decoder
could take on this chip over the time it took:
harness/opsbytes_conv.conv_decoder_step (every weight here once, the held
experts HIT and the picks routed to them from the program's frames, the live
K/V rows of the ten attention layers, the conv state of the rows that
advanced read and written) by harness/peaks.py, over step_device_ms. The
other families' shares count their own blocks; this is the same share for
the fifth."""


from harness.opsbytes_conv import conv_decoder_step, least_seconds
from harness.scopes_conv import published, step_ctx_tokens, step_means


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    m = step_means(o)
    if not fam or not m:
        return None
    flops, nbytes = conv_decoder_step(**published(o), rows=m["rows"], ctx_tokens=step_ctx_tokens(o, m["rows"]),
                                      experts_hit=m["experts_hit"], local_picks=m["local_picks"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
