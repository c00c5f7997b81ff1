"""Programs compiled inside the window by the decode scheduler
(recompiles_since_warmup at the window's edges); 0 expected."""


def read(o):
    return o["after"]["recompiles"] - o["before"]["recompiles"]
