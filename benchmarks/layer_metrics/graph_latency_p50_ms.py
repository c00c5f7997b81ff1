"""Median latency of a prediction on the client's clock."""


from harness.estimators import quantile


def read(o):
    q = quantile([r["done"] - r["sent"] for r in o["ended"] if not r.get("error")], 0.5)
    return q and 1e3 * q["value"]
