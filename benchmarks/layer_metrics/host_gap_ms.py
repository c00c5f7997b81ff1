"""Host time per scheduler round in which no dispatch was in flight (the
flight recorder's gap total over its rounds)."""


def read(o):
    rounds = o["after"]["rounds"] - o["before"]["rounds"]
    return (o["after"]["gap_ns"] - o["before"]["gap_ns"]) / 1e6 / rounds if rounds else None
