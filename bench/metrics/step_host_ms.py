"""Mean time of the traced window's ``serve.step`` spans outside their
``serve.*.sync`` children: the host's own work per scheduler step, in ms
(``bench/layers.step_host_ms``)."""
from bench import layers


def read(rec):
    return None if rec.trace is None else layers.step_host_ms(rec.trace)
