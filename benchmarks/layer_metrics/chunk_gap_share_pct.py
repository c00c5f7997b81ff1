"""Share of token gaps that contain a prefill chunk: tokens emitted by
flight-recorder rounds that ran a chunk dispatch over tokens of all decode
rounds in the window. itl_p95_ms sits on the step+chunk plateau while this
is well above 5."""


def read(o):
    s = o.get("chunk_gap_share")
    return None if s is None else 100.0 * s
