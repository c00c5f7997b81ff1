"""Median gap between consecutive token events of one request, client clock."""


from harness.estimators import quantile


def read(o):
    q = quantile(o["gaps"], 0.5)
    return q and 1e3 * q["value"]
