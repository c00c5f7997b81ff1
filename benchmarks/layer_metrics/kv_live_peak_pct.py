"""Peak share of the page pool held by live slots over the window's rounds
(flight frames' kv_live over the pool's pages less the junk page)."""


def read(o):
    if not o["frames"]:
        return None
    return 100.0 * max(f.kv_live for f in o["frames"]) / (o["after"]["pages"] - 1)
