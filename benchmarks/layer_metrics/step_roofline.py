"""The least time one fused decode step could take on this chip over the time
it took: max(ops / peak FLOP/s, bytes / peak bytes/s) by the peaks table and
harness/opsbytes.decoder_step, over step_device_ms. A step of 16 tokens
against 3.1 GB of float32 weights is memory-bound: bytes bind."""


from harness.opsbytes import decoder_step
from harness.peaks import device_peak


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    steps = o["after"]["steps"] - o["before"]["steps"]
    if not fam or not steps:
        return None
    g = o["geometry"]
    slots = (o["after"]["occupancy_sum"] - o["before"]["occupancy_sum"]) / steps
    n_slots = int(o["config"]["deployment"]["spec"]["predictors"][0]["tpu"]["decode_slots"])
    # context a generating slot attends over: its prompt plus half its output, on average
    ctx = slots * n_slots * (int(o["traffic"]["prompt_len"]) + 0.5 * _mean_output(o))
    flops, nbytes = decoder_step(hidden=g["hidden"], layers=g["layers"], ffn=g["ffn"], vocab=g["vocab"],
                                 slots=slots * n_slots, ctx_tokens=ctx)
    kind = o["device"]["kind"]
    least = max(flops / device_peak(kind, "bf16_flops_per_s"), nbytes / device_peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / fam["mean_s"]


def _mean_output(o):
    done = [r["gen_len"] for r in o["requests"] if r.get("gen_len")]
    return sum(done) / len(done) if done else 0.0
