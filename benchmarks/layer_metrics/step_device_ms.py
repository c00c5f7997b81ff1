"""Device time of one fused decode step (jit__fused_step module events in the
trace), mean over the traced slice."""


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("step")
    return fam and 1e3 * fam["mean_s"]
