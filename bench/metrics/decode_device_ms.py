"""Device time of one decode sub-step in the traced window: the
``decode_step`` module's time over its executions times the sub-steps each
fuses, in ms (``bench/layers.decode_device_ms``)."""
from bench import layers


def read(rec):
    return None if rec.trace is None else layers.decode_device_ms(rec.trace, rec.steps_per_admit)
