"""Mean wait from submit to slot assignment over the window's admissions:
the flight frames' admit_wait_ns over their admitted counts. The first half
of the time to first token as the program sees it. None where the frames
carry no such counter."""


def read(o):
    frames = [f for f in o["frames"] if getattr(f, "admit_wait_ns", None) is not None]
    n = sum(f.admitted for f in frames)
    return sum(f.admit_wait_ns for f in frames) / 1e6 / n if n else None
