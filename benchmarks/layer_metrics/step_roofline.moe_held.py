"""The least time one fused decode step of the sparse-expert decoder with a
held share could take on this chip over the time it took:
harness/opsbytes_moe_held.moe_held_step (attention weights of both head
counts, the gate, the dense layer, router, shared expert, the held experts
HIT and the picks that landed on them from the program's frames, the head's
slice, and the K/V rows a layer of each kind has to read: the context on a
full layer, the window on a sliding one) by harness/peaks.py, over
step_device_ms. ``step_roofline.moe`` counts the block without any of these."""


from harness.opsbytes_moe_held import least_seconds, moe_held_step
from harness.scopes_win import held_share, step_means


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    p, m = held_share(o), step_means(o)
    if not fam or not p or not m:
        return None
    done = [r["gen_len"] for r in o["requests"] if r.get("gen_len")]
    # context a generating slot attends over: its prompt plus half its output, on average
    ctx = m["rows"] * (int(o["traffic"]["prompt_len"]) + 0.5 * (sum(done) / len(done) if done else 0.0))
    flops, nbytes = moe_held_step(**p, experts_hit=m["experts_hit"], local_picks=m["local_picks"], rows=m["rows"], ctx_tokens=ctx)
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / fam["mean_s"]
