"""95th percentile of the gap between consecutive token events of one
request, client clock, pooled over the window; first tokens make no gap."""


from harness.estimators import quantile


def read(o):
    q = quantile(o["gaps"], 0.95)
    return q and 1e3 * q["value"]
