"""Token events inside the window over the window's length: the estimator
that gains or loses a whole round at each edge, beside the aligned one."""


def read(o):
    inside = [t for t in o["token_times"] if o["t0"] <= t < o["t1"]]
    return len(inside) / o["seconds"]
