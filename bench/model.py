"""A configuration file as the program runs it, and its weights.

A configuration is a JSON file under ``bench/configs`` that holds the
published ``config.json`` keys as they are run (``reduced`` names each key
changed from the source, with its published value). This module turns it
into the program's ``ModelConfig`` for a cell's FedAttn participants N and
sync interval H, and draws the weights from the run's seed.

The weights are the benchmark's own: one jitted call on the device draws
them in the served dtype, in the layout below, and :func:`program_params`
hands the same arrays to the program in the tree its scan-form entry points
consume. The reference (``bench/reference.py``) reads the benchmark layout
and nothing the program made.

Benchmark layout (P = n_layers / H sync periods, one dict per layer slot of
a period, each leaf with a leading (P,) axis; layer ``p*H + i`` is slot
``i`` of period ``p``)::

    {"embed": (Vp, d), "final_ln": (d,), "head": (d, Vp) or absent,
     "layers": [{"ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                 "ln2", "wg", "wu", "wd"}] * H}
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

@dataclass(frozen=True)
class Dims:
    """The sizes the harness, the FLOP count and the reference need."""

    n_layers: int
    d: int
    n_q: int
    n_kv: int
    d_head: int
    d_ff: int
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


def dims(cfg: dict, participants: int, sync_interval: int) -> Dims:
    if cfg.get("hidden_act") != "silu" or cfg.get("model_type") != "qwen2":
        raise ValueError(
            f"{cfg.get('name')}: only qwen2-family configurations (SwiGLU, "
            "RMSNorm, GQA with q/k/v bias) are drawn by this module"
        )
    n_layers = int(cfg["num_hidden_layers"])
    if n_layers % sync_interval:
        raise ValueError(f"{n_layers} layers are not whole periods of H={sync_interval}")
    d, n_q = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    vocab = int(cfg["vocab_size"])
    rows = vocab if vocab % 512 == 0 or vocab < 512 else vocab + (-vocab) % 512
    return Dims(
        n_layers=n_layers, d=d, n_q=n_q,
        n_kv=int(cfg["num_key_value_heads"]), d_head=d // n_q,
        d_ff=int(cfg["intermediate_size"]), vocab=vocab, vocab_rows=rows,
        tied=bool(cfg["tie_word_embeddings"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), dtype=str(cfg["torch_dtype"]),
        sync_interval=sync_interval, participants=participants,
    )


def program_config(cfg: dict, dm: Dims):
    """The program's ``ModelConfig`` for this configuration and cell."""
    from repro.types import FedAttnConfig, LayerSpec, ModelConfig

    H = dm.sync_interval
    return ModelConfig(
        name=cfg["name"], arch_type="dense", n_layers=dm.n_layers,
        d_model=dm.d, n_heads=dm.n_q, n_kv_heads=dm.n_kv, d_head=dm.d_head,
        d_ff=dm.d_ff, vocab_size=dm.vocab, qkv_bias=True,
        rope_theta=dm.theta, norm_eps=dm.eps, tie_embeddings=dm.tied,
        dtype=dm.dtype,
        pattern=tuple(LayerSpec(kind="attn", sync=(i == H - 1)) for i in range(H)),
        fedattn=FedAttnConfig(n_participants=dm.participants, sync_interval=H),
        source=cfg["source"],
    )


def seeds(seed: int) -> tuple[int, np.random.Generator]:
    """The run's two streams from ``--seed`` (any non-negative integer,
    also past 32 bits): a 31-bit key for the weights and a numpy generator
    for the traffic's token ids and the check's sample."""
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    ss = np.random.SeedSequence(seed)
    w_key = int(ss.generate_state(1)[0] >> 1)
    return w_key, np.random.default_rng(ss.spawn(1)[0])


def _draw(key, dm: Dims):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dm.dtype)
    P, H = dm.n_periods, dm.sync_interval
    d, f, dh = dm.d, dm.d_ff, dm.d_head
    q, kv = dm.n_q * dh, dm.n_kv * dh
    keys = iter(jax.random.split(key, 4 + 12 * H))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)

    layers = []
    for _ in range(H):
        layers.append({
            "ln1": scale((P, d)),
            "wq": normal((P, d, q), d ** -0.5), "bq": normal((P, q), 0.1),
            "wk": normal((P, d, kv), d ** -0.5), "bk": normal((P, kv), 0.1),
            "wv": normal((P, d, kv), d ** -0.5), "bv": normal((P, kv), 0.1),
            "wo": normal((P, q, d), q ** -0.5),
            "ln2": scale((P, d)),
            "wg": normal((P, d, f), d ** -0.5), "wu": normal((P, d, f), d ** -0.5),
            "wd": normal((P, f, d), f ** -0.5),
        })
    w = {
        "embed": normal((dm.vocab_rows, d), d ** -0.5),
        "final_ln": scale((d,)),
        "layers": layers,
    }
    if not dm.tied:
        w["head"] = normal((d, dm.vocab_rows), d ** -0.5)
    return w


def make_weights(dm: Dims, w_key: int):
    """Every weight drawn on the device by one jitted call, in the served
    dtype (bfloat16 for both configurations)."""
    import functools

    import jax

    return jax.jit(functools.partial(_draw, dm=dm))(jax.random.key(w_key))


def program_params(w: dict) -> dict:
    """The same arrays in the program's scan-form tree (no copy)."""
    return {
        "embed": {"tok": w["embed"]},
        "stacked": [
            {
                "norm1": {"scale": s["ln1"]},
                "attn": {k: s[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
                "norm2": {"scale": s["ln2"]},
                "ffn": {"w_gate": s["wg"], "w_up": s["wu"], "w_down": s["wd"]},
            }
            for s in w["layers"]
        ],
        "remainder": [],
        "final_norm": {"scale": w["final_ln"]},
        "head": {"w": w["head"]} if "head" in w else {},
    }
