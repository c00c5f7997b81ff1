"""Mean share of decode slots busy per decode step in the window (the
scheduler's stat_occupancy_sum over stat_steps)."""


def read(o):
    steps = o["after"]["steps"] - o["before"]["steps"]
    return (o["after"]["occupancy_sum"] - o["before"]["occupancy_sum"]) / steps if steps else None
