"""Median time from when a request was DUE to its first token event, over
every request due in the window; one that failed or got no first token
before the grace ran out counts as infinitely late."""


from harness.estimators import quantile


def read(o):
    vals = [
        (r["token_times"][0] - r["due"]) if r["token_times"] and not r.get("error") else float("inf")
        for r in o["measured"]
    ]
    q = quantile(vals, 0.5)
    return q and 1e3 * q["value"]
