"""Mean wall of the window's step() calls that admitted nothing, per
decode sub-step, in ms."""


def read(rec):
    s = rec.decode_step_s()
    return None if s is None else s * 1e3
