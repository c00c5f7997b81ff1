"""Predictions answered 200 inside the window, per second."""


def read(o):
    ok = [r for r in o["ended"] if not r.get("error")]
    return len(ok) * int(o["traffic"].get("rows", 1)) / o["seconds"]
