"""Device idle time per scheduler round while the loop thread wrote an SSE
flush (decode.sse_write) or was inside a round but in none of its named
states (decode.round only: other tasks of the event loop, the yields
between phases)."""


from harness.scopes import ROUND_ONLY, SSE_WRITE, idle_ms_per_round


def read(o):
    return idle_ms_per_round(o, SSE_WRITE, ROUND_ONLY)
