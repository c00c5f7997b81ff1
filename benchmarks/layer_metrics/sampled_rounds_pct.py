"""Share of the window's rounds in which a dispatch had a row that samples:
the program's own count (FlightFrame ``sample_rows`` > 0; the scheduler counts
the rows of each dispatch's ``temps`` the sampler's gate reads). 0 where every
request is greedy, so that every dispatch computed the argmax and nothing
else. A program without the counter (the parent of PR 35) gives None."""


def read(o):
    fs = [f for f in o.get("frames") or [] if hasattr(f, "sample_rows")]
    if not fs:
        return None
    return 100.0 * sum(1 for f in fs if f.sample_rows > 0) / len(fs)
