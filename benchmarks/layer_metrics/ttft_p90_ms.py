"""90th percentile of due-to-first-token over every request due in the window
(failed = infinitely late), nearest rank: the one tail reader of TTFT. With
408 requests a window it has 40 samples beyond it (at 51 it had four or
five); reported, not bounded: it is the tail of a queue at 0.84 of its knee,
and each ~105 ms stall of the machine puts a handful of requests into it
(261-448 ms over six runs; PERF.md section 2)."""


from harness.estimators import quantile


def read(o):
    vals = [
        (r["token_times"][0] - r["due"]) if r["token_times"] and not r.get("error") else float("inf")
        for r in o["measured"]
    ]
    q = quantile(vals, 0.9)
    return q and 1e3 * q["value"]
