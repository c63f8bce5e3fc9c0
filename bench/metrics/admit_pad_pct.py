"""Share of the window's admission prefill rows x bucketed lengths that
were padding: 100 x (padded - real) / padded prompt tokens, from the
change of the scheduler's ``prefill_padded_tokens`` and
``prefill_tokens`` counters over the window, in %
(``bench/layers.admit_pad_pct``)."""
from bench import layers


def read(rec):
    return layers.admit_pad_pct(rec.counters)
