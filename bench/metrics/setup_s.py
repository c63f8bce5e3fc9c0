"""Process start to the first measured instant: imports, weights, the
compile cache, the warm-up, and the traffic's pre-roll or ramp."""


def read(rec):
    return rec.setup_s
