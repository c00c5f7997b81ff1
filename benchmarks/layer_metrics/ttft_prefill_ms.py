"""Mean time from slot assignment to the first token over the window's first
emissions: the flight frames' prefill_ns over their first_tokens. The second
half of the time to first token as the program sees it. None where the
frames carry no such counter."""


def read(o):
    frames = [f for f in o["frames"] if getattr(f, "prefill_ns", None) is not None]
    n = sum(f.first_tokens for f in frames)
    return sum(f.prefill_ns for f in frames) / 1e6 / n if n else None
