"""Device time of one BERT forward dispatch (the serving program's module
events in the trace), mean over the traced slice."""


def read(o):
    fam = (o["trace"] or {}).get("families", {}).get("forward")
    return fam and 1e3 * fam["mean_s"]
