"""Qwen2 (``model_type`` ``qwen2``): RMSNorm, RoPE, GQA with q/k/v bias,
SwiGLU, tied or untied head.

The sizes, the program's ``ModelConfig``, the weight draw, the program's
tree, the float32 reference layer and the FLOPs of a layer, as
``bench/model.py`` lists them for an architecture module.

Layer slot of the benchmark layout (each leaf with a leading (P,) axis)::

    {"ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2", "wg", "wu", "wd"}
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench import model
from bench.reference import attend, mm, rms

MODEL_TYPE = "qwen2"


@dataclass(frozen=True)
class Dims(model.Dims):
    d_ff: int


def dims(cfg: dict, participants: int, sync_interval: int) -> Dims:
    if cfg.get("hidden_act") != "silu":
        raise ValueError(f"{cfg.get('name')}: Qwen2's MLP is SwiGLU (hidden_act silu)")
    d, n_q = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    vocab = int(cfg["vocab_size"])
    return Dims(
        model_type=MODEL_TYPE, n_layers=int(cfg["num_hidden_layers"]), d=d, n_q=n_q,
        n_kv=int(cfg["num_key_value_heads"]), d_head=d // n_q,
        d_ff=int(cfg["intermediate_size"]), vocab=vocab, vocab_rows=model.vocab_rows(vocab),
        tied=bool(cfg["tie_word_embeddings"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), dtype=str(cfg["torch_dtype"]),
        sync_interval=sync_interval, participants=participants,
    )


def program_config(cfg: dict, dm: Dims):
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


def draw(key, dm: Dims):
    """Matrices normal with std fan_in^-0.5, biases normal std 0.1, norm
    scales 1 + 0.1 normal, embedding and head normal std d^-0.5."""
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


def program_params(w: dict, dm: Dims) -> dict:
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


def rope(x, pos, theta):
    """Rotate-half RoPE. x (T, heads, dh), pos (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv  # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dm", "sync", "quant"))
def _layer(x, lw, pos, seg, *, dm: Dims, sync: bool, quant):
    T = x.shape[0]
    dh = dm.d_head
    h = rms(x, lw["ln1"], dm.eps)
    q = (mm(h, lw["wq"], quant) + lw["bq"].astype(jnp.float32)).reshape(T, dm.n_q, dh)
    k = (mm(h, lw["wk"], quant) + lw["bk"].astype(jnp.float32)).reshape(T, dm.n_kv, dh)
    v = (mm(h, lw["wv"], quant) + lw["bv"].astype(jnp.float32)).reshape(T, dm.n_kv, dh)
    q, k = rope(q, pos, dm.theta), rope(k, pos, dm.theta)
    x = x + mm(attend(q, k, v, pos, seg, sync=sync), lw["wo"], quant)
    h = rms(x, lw["ln2"], dm.eps)
    f = jax.nn.silu(mm(h, lw["wg"], quant)) * mm(h, lw["wu"], quant)
    return x + mm(f, lw["wd"], quant)


def layer(x, lw, pos, seg, *, dm: Dims, index: int, quant):
    """Layer ``index`` in float32; local and sync layers compile apart."""
    return _layer(x, lw, pos, seg, dm=dm, sync=dm.is_sync(index), quant=quant)


def weight_flops(dm: Dims, index: int) -> int:
    """q, k, v, o and the three SwiGLU matrices, per token."""
    q, kv = dm.n_q * dm.d_head, dm.n_kv * dm.d_head
    return 2 * (dm.d * q + 2 * dm.d * kv + q * dm.d + 3 * dm.d * dm.d_ff)


def pair_flops(dm: Dims, index: int) -> int:
    """q.k and p.v of every query head, per visible (query, key) pair."""
    return 4 * dm.n_q * dm.d_head
