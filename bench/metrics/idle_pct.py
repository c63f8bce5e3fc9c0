"""Idle share of the device in the traced window, in %: 1 - the union of
device-op time over the window, leaving out the spans in which the harness
waited for an arrival with nothing resident or queued (bench/trace.py,
the ``base`` of the layered summary)."""


def read(rec):
    return None if rec.trace is None else rec.trace.base.idle_pct
