"""The least time one fused decode step of the hybrid decoder could take on
this chip over the time it took: harness/opsbytes_ssm.hybrid_decoder_step
(every weight once, the state of the rows the program's frames say
generated read and written, the K/V rows of the four attention layers) by
harness/peaks.py, over step_device_ms. ``step_roofline``'s count
(opsbytes.decoder_step) is the GPT-2 block's and ``step_roofline.moe``'s the
sparse-expert one's; this is the same share for the third family."""


from harness.opsbytes_ssm import hybrid_decoder_step, least_seconds
from harness.scopes_ssm import published, step_rows


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    rows = step_rows(o)
    if not fam or not rows:
        return None
    done = [r["gen_len"] for r in o["requests"] if r.get("gen_len")]
    # context a generating slot attends over: its prompt plus half its output, on average
    ctx = rows * (int(o["traffic"]["prompt_len"]) + 0.5 * (sum(done) / len(done) if done else 0.0))
    flops, nbytes = hybrid_decoder_step(**published(o), rows=rows, ctx_tokens=ctx)
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
