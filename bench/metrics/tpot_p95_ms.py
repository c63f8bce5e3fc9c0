"""95th percentile, over the window's requests (``Record.window_reqs``),
of (last token - first token) / (n_new - 1), in ms."""
import numpy as np


def read(rec):
    xs = [(r.t_last - r.t_first) / (r.n_new - 1)
          for r in rec.window_reqs() if r.t_last is not None and r.n_new > 1]
    return float(np.percentile(xs, 95)) * 1e3 if xs else None
