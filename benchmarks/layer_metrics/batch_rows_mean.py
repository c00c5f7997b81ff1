"""Rows per merged batch the micro-batcher formed in the window (its counters)."""


def read(o):
    batches = o["after"]["batches"] - o["before"]["batches"]
    return (o["after"]["rows"] - o["before"]["rows"]) / batches if batches else None
