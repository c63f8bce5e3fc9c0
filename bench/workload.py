"""What every traffic generator shares: the request record the harness
clocks, and the cell's fixed set of sizes.

A cell's sizes (prompt length, tokens to generate) are drawn once from the
cell's own ``sizes_seed``, so every run of a cell serves the same set of
sizes; ``--seed`` only orders them and draws the token ids. Runs on
different seeds then do the same work in another order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass
class Req:
    """One request as the harness sees it. Times are ``perf_counter``
    seconds; ``None`` until they happen."""

    idx: int
    tokens: np.ndarray  # (L,) int32 prompt
    n_new: int
    due: float = math.inf  # when the client wanted to send it
    client: int = -1  # closed loop: the client that sent it
    rid: Optional[int] = None  # the scheduler's id
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None  # start of the step() that admitted it
    t_first: Optional[float] = None  # end of that step()
    t_last: Optional[float] = None  # end of the step() after which it was done
    emitted: int = 0
    phase: str = ""  # phase of the run in which it was due
    out_tokens: Optional[np.ndarray] = field(default=None, repr=False)
    out_logprobs: Optional[np.ndarray] = field(default=None, repr=False)

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])


def log_uniform(rng: np.random.Generator, lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` integers in [lo, hi], uniform in log."""
    x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), size=n))
    return np.clip(np.floor(x).astype(np.int64), lo, hi)


def fixed_sizes(cell: dict, n: int) -> list[tuple[int, int]]:
    """The cell's ``n`` (prompt length, n_new) pairs from its sizes seed."""
    rng = np.random.default_rng(cell["sizes_seed"])
    lens = log_uniform(rng, *cell["prompt_len"], n)
    news = log_uniform(rng, *cell["n_new"], n)
    return [(int(a), int(b)) for a, b in zip(lens, news)]


def prompt(rng: np.random.Generator, L: int, vocab: int) -> np.ndarray:
    """Token ids of a prompt; ids 0-2 are left out as special tokens."""
    return rng.integers(3, vocab, size=L, dtype=np.int64).astype(np.int32)
