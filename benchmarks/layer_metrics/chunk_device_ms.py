"""Device time of one fused prefill chunk (jit__fused_chunk module events in
the trace), mean over the traced slice."""


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("chunk")
    return fam and 1e3 * fam["mean_s"]
