"""Share of the traced slice's device idle time that falls in a named host
state of the program (enqueue, readback, a phase, an SSE write, the idle
wait, a dispatch's hand-off) rather than in a round outside all of them or
outside any round. None where the trace holds no decode.* annotation."""


from harness.scopes import of_run


def read(o):
    idle = (of_run(o) or {}).get("idle")
    return None if not idle or idle["named_share"] is None else 100.0 * idle["named_share"]
