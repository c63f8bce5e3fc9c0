"""A configuration file as the program runs it, its sizes and its weights.

A configuration is a JSON file under ``bench/configs`` that holds the
published ``config.json`` keys as they are run (``reduced`` names each key
changed from the source, with its published value). Its ``model_type``
names the architecture module ``bench/arch/<model_type>.py``, which turns
it into the sizes (a :class:`Dims`, or a subclass with fields of its own),
the program's ``ModelConfig`` for a cell's FedAttn participants N and sync
interval H, the weights drawn from the run's seed, the tree the program
consumes, the float32 reference layer and the FLOPs of a layer.

An architecture module provides::

    dims(cfg, participants, sync_interval) -> Dims   # with model_type set
    program_config(cfg, dm) -> repro.types.ModelConfig
    draw(key, dm) -> weights in the benchmark layout (below)
    program_params(w, dm) -> the same arrays in the program's tree
    layer(x, lw, pos, seg, *, dm, index, quant) -> x   # bench/reference.py
    weight_flops(dm, index) -> int   # a layer's weight FLOPs per token
    pair_flops(dm, index) -> int     # its attention FLOPs per visible pair

and, where its attention mask is not the plain FedAttn one, its own
``prefill_flops(dm, L)`` and ``decode_flops(dm, L, m_lo, m_hi)``
(``bench/flops.py``).

The weights are the benchmark's own: one jitted call on the device draws
them in the served dtype, and :func:`program_params` hands the same arrays
to the program in the tree its scan-form entry points consume. The
reference (``bench/reference.py``) reads the benchmark layout and nothing
the program made.

Benchmark layout (P = n_layers / H sync periods, one dict per layer slot of
a period, each leaf with a leading (P,) axis; layer ``p*H + i`` is slot
``i`` of period ``p``; the keys of a slot are the module's)::

    {"embed": (Vp, d), "final_ln": (d,), "head": (d, Vp) or absent,
     "layers": [{...}] * H}
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, bad cell)."""


@dataclass(frozen=True)
class Dims:
    """The sizes the harness, the FLOP count and the reference need.

    Frozen and hashable: the reference's jitted layers take it as a static
    argument. An architecture module may subclass it with fields of its
    own, frozen likewise."""

    model_type: str
    n_layers: int
    d: int
    n_q: int
    n_kv: int
    d_head: int
    vocab: int
    vocab_rows: int  # embedding rows, padded as the program pads them
    tied: bool
    eps: float
    theta: float
    dtype: str
    sync_interval: int
    participants: int

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.sync_interval

    def is_sync(self, layer: int) -> bool:
        """FedAttn sync layers: the last of every H layers (the program's
        uniform schedule over its repeating pattern)."""
        return layer % self.sync_interval == self.sync_interval - 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def arch(model_type: str):
    """The architecture module ``bench/arch/<model_type>.py``."""
    name = f"bench.arch.{model_type}"
    if not model_type.isidentifier():
        raise Refused(f"model_type {model_type!r} names no module")
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise Refused(f"no architecture module bench/arch/{model_type}.py "
                      f"for model_type {model_type!r}") from None


def vocab_rows(vocab: int) -> int:
    """Embedding rows as the program pads them: up to a multiple of 512."""
    return vocab if vocab % 512 == 0 or vocab < 512 else vocab + (-vocab) % 512


def dims(cfg: dict, participants: int, sync_interval: int) -> Dims:
    dm = arch(str(cfg.get("model_type"))).dims(cfg, participants, sync_interval)
    if dm.n_layers % sync_interval:
        raise ValueError(f"{dm.n_layers} layers are not whole periods of H={sync_interval}")
    return dm


def program_config(cfg: dict, dm: Dims):
    """The program's ``ModelConfig`` for this configuration and cell."""
    return arch(dm.model_type).program_config(cfg, dm)


def seeds(seed: int) -> tuple[int, np.random.Generator]:
    """The run's two streams from ``--seed`` (any non-negative integer,
    also past 32 bits): a 31-bit key for the weights and a numpy generator
    for the traffic's token ids and the check's sample."""
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    ss = np.random.SeedSequence(seed)
    w_key = int(ss.generate_state(1)[0] >> 1)
    return w_key, np.random.default_rng(ss.spawn(1)[0])


def make_weights(dm: Dims, w_key: int):
    """Every weight drawn on the device by one jitted call, in the served
    dtype."""
    import functools

    import jax

    draw = arch(dm.model_type).draw
    return jax.jit(functools.partial(draw, dm=dm))(jax.random.key(w_key))


def program_params(w: dict, dm: Dims) -> dict:
    """The same arrays in the program's scan-form tree (no copy)."""
    return arch(dm.model_type).program_params(w, dm)
