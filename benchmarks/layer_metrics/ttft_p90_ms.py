"""90th percentile of due-to-first-token over every request due in the window
(failed = infinitely late). With ~45 requests a window it has four or five
samples beyond it: reported, not bounded."""


from harness.estimators import quantile


def read(o):
    vals = [
        (r["token_times"][0] - r["due"]) if r["token_times"] and not r.get("error") else float("inf")
        for r in o["measured"]
    ]
    q = quantile(vals, 0.9)
    return q and 1e3 * q["value"]
