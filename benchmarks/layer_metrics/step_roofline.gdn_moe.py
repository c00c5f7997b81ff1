"""The least time one fused decode step of the hybrid decoder's third shape
could take on this chip over the time it took:
harness/opsbytes_gdn.gdn_moe_step (every delta-rule and attention weight
once, the router, the shared expert and its gate of each expert layer, the
held experts HIT and the picks that landed on them from the program's frames,
the head's slice; the matrix state and conv cache of the rows the frames say
generated read and written once; the K/V rows of the attention layers) by
harness/peaks.py, over step_device_ms. ``step_roofline.ssm_moe`` counts the
family's second shape; this is the same share of the whole step for the
third."""


from harness.opsbytes_gdn import gdn_moe_step, least_seconds
from harness.scopes_gdn import published, step_ctx_tokens, step_means


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    p, m = published(o), step_means(o)
    if not fam or not p or not m:
        return None
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    flops, nbytes = gdn_moe_step(**sizes, rows=m["rows"], ctx_tokens=step_ctx_tokens(o, m["rows"]),
                                 experts_hit=m["experts_hit"], local_picks=m["local_picks"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
