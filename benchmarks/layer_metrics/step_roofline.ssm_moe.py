"""The least time one fused decode step of the hybrid decoder with single
sublayers and held experts could take on this chip over the time it took:
harness/opsbytes_ssm_moe.ssm_moe_step (every Mamba-2 and attention weight
once, the router and the shared expert of each expert layer, the held experts
HIT and the picks that landed on them from the program's frames at their
published width of 1856 whatever is stored, the head's slice; the state and
conv cache of the rows the frames say generated read and written; the K/V
rows of the attention layers) by harness/peaks.py, over step_device_ms.
``step_roofline.ssm`` counts granite's block (a dense MLP a layer, one B/C
group, a tied head); this is the same share for the family's other shape."""


from harness.opsbytes_ssm_moe import least_seconds, ssm_moe_step
from harness.scopes_ssm_moe import published, step_ctx_tokens, step_means


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    p, m = published(o), step_means(o)
    if not fam or not p or not m:
        return None
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    flops, nbytes = ssm_moe_step(**sizes, rows=m["rows"], ctx_tokens=step_ctx_tokens(o, m["rows"]),
                                 experts_hit=m["experts_hit"], local_picks=m["local_picks"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
