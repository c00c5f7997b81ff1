"""Mean wait of a request in the micro-batcher's queue (its counters)."""


def read(o):
    items = o["after"]["items"] - o["before"]["items"]
    return 1e3 * (o["after"]["queue_wait_s"] - o["before"]["queue_wait_s"]) / items if items else None
