"""Process start to the start of the measured window: imports, weights, compile
or cache load, warm-up, priming, the reference check, the load child's ramp."""


def read(o):
    return o["setup_s"]
