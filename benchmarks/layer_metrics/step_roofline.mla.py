"""The least time one fused decode step of the latent-attention decoder could
take on this chip over the time it took: harness/opsbytes_mla.mla_decoder_step
(the layers' weights, the held experts HIT and the rows and picks routed to
them from the program's frames, the head's slice, the latent rows the
generating slots attended over read once a layer) by harness/peaks.py, over
step_device_ms. The other families' shares count their own blocks; this is
the same share for the fourth."""


from harness.opsbytes_mla import least_seconds, mla_decoder_step
from harness.scopes_mla import published, step_means


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    m = step_means(o)
    if not fam or not m:
        return None
    flops, nbytes = mla_decoder_step(**published(o), rows=m["rows"], ctx_rows=m["ctx_rows"],
                                     experts_hit=m["experts_hit"], local_picks=m["local_picks"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
