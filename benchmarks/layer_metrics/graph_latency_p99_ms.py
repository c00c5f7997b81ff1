"""99th percentile of a prediction's latency on the client's clock."""


from harness.estimators import quantile


def read(o):
    q = quantile([r["done"] - r["sent"] for r in o["ended"] if not r.get("error")], 0.99)
    return q and 1e3 * q["value"]
