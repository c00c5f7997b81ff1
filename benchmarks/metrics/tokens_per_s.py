"""Output tokens delivered over SSE per second, edge-aligned: of the token
events inside the window the first emission burst only anchors the clock;
the tokens of every later burst are counted and divided by the time from
the first burst to the last (harness/estimators.aligned_rate)."""


from harness.estimators import aligned_rate


def read(o):
    rate = aligned_rate(o["token_times"], o["t0"], o["t1"])
    return rate and rate["value"]
