"""Gateway + fast ingress + wire: the client's latency minus the request's
root span in the span store (service ingress -> batcher -> executor),
median over the window's requests. Traced runs keep every request's spans."""


from harness.estimators import quantile


def read(o):
    if not o["spans"]:
        return None
    diffs = [
        1e3 * (r["done"] - r["sent"]) - o["spans"][r["puid"]]
        for r in o["ended"]
        if not r.get("error") and r.get("puid") in o["spans"]
    ]
    q = quantile(diffs, 0.5)
    return q and q["value"]
