"""Median time from when a request was DUE to its first token event, over
every request due in the window; one that failed or got no first token
before the grace ran out counts as infinitely late. Since PR 31 the
open-loop cell offers 408 requests a window, so the median has two hundred
samples on either side; it stays per-layer because it still moves 2% (11%
in a window that one of the machine's long stalls falls into) from run to
run: a queue near its knee repeats what the host does (PERF.md section 2)."""


from harness.estimators import quantile


def read(o):
    vals = [
        (r["token_times"][0] - r["due"]) if r["token_times"] and not r.get("error") else float("inf")
        for r in o["measured"]
    ]
    q = quantile(vals, 0.5)
    return q and 1e3 * q["value"]
