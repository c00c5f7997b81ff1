"""Programs compiled inside the window by the graph tier's model runtimes (jit
cache sizes at the window's edges); 0 expected."""


def read(o):
    return o["after"]["programs"] - o["before"]["programs"]
