"""Device time of the admission entry points (``prefill``,
``suffix_prefill``, ``admit_finish``, ``slot_write``) in the traced window
per thousand real prompt tokens the scheduler prefilled in it (the change
of its ``prefill_tokens`` counter), in ms
(``bench/layers.prefill_ms_per_ktok``)."""
from bench import layers


def read(rec):
    if rec.trace is None:
        return None
    return layers.prefill_ms_per_ktok(rec.trace, rec.counters.get("prefill_tokens", 0))
