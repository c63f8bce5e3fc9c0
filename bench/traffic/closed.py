"""Closed loop: ``clients`` callers, each sending its next request the
moment its previous one completes.

The loop starts as it would be found at a random moment of its steady
state, so that the window measures that state and not the first cohort of
requests finishing together: each client's first request is a size picked
in proportion to its ``n_new`` (a slot holds a long request longer) and cut
to a uniform share of it (the tokens it still had to go). These first
sizes, like the later ones, are a fixed set drawn from the cell's sizes
seed; ``--seed`` orders both.

The clients join ``ramp_per_tick`` at a time, less the re-submissions of
that tick, so no admission is wider than the widths the warm-up compiled.
Later requests take the cell's fixed sizes one after another from a shared
cursor.
"""
from __future__ import annotations

import math
from collections import deque

import numpy as np

from bench.workload import Req, fixed_sizes, prompt


def first_sizes(cell: dict, sizes: list) -> list[tuple[int, int]]:
    """Each client's first (prompt length, n_new): the work a slot still
    holds at a random moment of the steady state."""
    rng = np.random.default_rng([cell["sizes_seed"], 1])
    n_new = np.array([n for _, n in sizes], np.float64)
    picks = rng.choice(len(sizes), size=cell["clients"], p=n_new / n_new.sum())
    shares = rng.uniform(size=cell["clients"])
    return [(sizes[i][0], max(2, math.ceil(f * sizes[i][1]))) for i, f in zip(picks, shares)]


class Traffic:
    def __init__(self, cell: dict, rng: np.random.Generator, vocab: int, seconds: float):
        self.cell = cell
        self.rng = rng
        self.vocab = vocab
        self.sizes = fixed_sizes(cell, cell["n_sizes"])
        first = first_sizes(cell, self.sizes)
        self._first = [first[i] for i in rng.permutation(cell["clients"])]
        self._order = rng.permutation(len(self.sizes))
        self._next = 0
        self._n = 0
        self._waiting = deque(range(cell["clients"]))  # clients yet to start
        self._ready: deque = deque()  # (client, due) re-submissions

    def _new(self, client: int, due: float, size) -> Req:
        L, n_new = size
        r = Req(idx=self._n, tokens=prompt(self.rng, L, self.vocab),
                n_new=n_new, due=due, client=client)
        self._n += 1
        return r

    def pop_due(self, now: float) -> list[Req]:
        out = []
        for c, due in self._ready:
            out.append(self._new(c, due, self.sizes[self._order[self._next % len(self.sizes)]]))
            self._next += 1
        self._ready.clear()
        for _ in range(min(max(0, self.cell["ramp_per_tick"] - len(out)), len(self._waiting))):
            c = self._waiting.popleft()
            out.append(self._new(c, now, self._first[c]))
        return out

    def next_due(self):
        return None  # a closed loop never waits on a schedule

    def completed(self, req: Req, now: float) -> None:
        self._ready.append((req.client, now))

    @property
    def ramped(self) -> bool:
        return not self._waiting
