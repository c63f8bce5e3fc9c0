"""The closed loop's traffic: every seed offers the same work in another order."""
import numpy as np

from bench.traffic import closed
from conftest import CELLS


def _sizes(reqs):
    return sorted((r.prompt_len, r.n_new) for r in reqs)


def test_closed_loop_first_requests_are_a_fixed_set_of_residual_sizes():
    cell = CELLS["tiny.closed"]
    firsts = []
    for seed in (1, 2**31 + 5):
        t = closed.Traffic(cell, np.random.default_rng(seed), 700, 1.0)
        reqs = []
        while not t.ramped:
            reqs += t.pop_due(0.0)
        firsts.append(_sizes(reqs))
    assert firsts[0] == firsts[1]
    assert all(n >= 2 and any(L == L0 and n <= n0 for L0, n0 in t.sizes)
               for L, n in firsts[0])


def test_closed_loop_joins_fewer_clients_while_others_resubmit():
    cell = dict(CELLS["tiny.closed"], clients=6, ramp_per_tick=3)
    t = closed.Traffic(cell, np.random.default_rng(0), 700, 1.0)
    first = t.pop_due(0.0)
    assert len(first) == 3
    t.completed(first[0], 1.0)
    t.completed(first[1], 1.0)
    assert len(t.pop_due(1.0)) == 3  # two re-submissions and one new client
    assert len(t.pop_due(2.0)) == 2  # the last two clients
    assert t.ramped
