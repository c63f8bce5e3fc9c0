"""Share of the ``decode_step`` module's leaf-op time in the traced window
that runs under the model's ``decode_attention`` scope (the paged read,
the grouped contractions and the softmax), in %
(``bench/layers.decode_attn_pct``)."""
from bench import layers


def read(rec):
    return None if rec.trace is None else layers.decode_attn_pct(rec.trace)
