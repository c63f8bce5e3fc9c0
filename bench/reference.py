"""The plain reference: a float32 FedAttn forward of the configuration's
architecture.

What every architecture shares is here: positions and participant
segments, the FedAttn visibility rule (``bench/flops.py`` states it;
:func:`visible`), attention over it in blocks of query rows
(:func:`attend`), padding, the embedding and the head, matrix products at
``precision=HIGHEST`` (:func:`mm`), RMSNorm (:func:`rms`) and the fp8
control's rounding. Each layer's forward is the architecture module's
(``bench/arch/<model_type>.py``: ``layer``), written from the published
architecture in plain ``jax.numpy`` on these helpers. Nothing here imports
the program; it reads only the benchmark's own weights (``bench/model.py``
layout).

One departure from the published models follows the program: embedded
tokens are scaled by sqrt(hidden_size) (see the configuration files'
``assumed``).

It runs layer by layer, one jitted layer at a time with the layer's
weights cast to float32 inside, and attends in blocks of query rows, so
that a sequence of some thousands of tokens fits beside the weights.

``quant="fp8"`` is the control: the same forward with the inputs of every
weight product (activations per row, weights per output column) rounded
to float8_e4m3fn with a scale, as an fp8 serving path would compute them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import model
from bench.flops import segment_sizes

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block
PAD_T = 512  # sequence lengths are padded to a multiple of this
PAD_OUT = 64  # output rows likewise
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fake_fp8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def visible(pos_q, seg_q, pos_k, seg_k, sync: bool):
    """The FedAttn mask of queries at (pos_q, seg_q) over keys at (pos_k,
    seg_k): causal; keys of segment -1 (padding) are no one's; a local
    layer's query sees its own participant's keys only, a sync layer's
    every earlier key."""
    vis = pos_q[:, None] >= pos_k[None, :]
    vis &= seg_k[None, :] >= 0
    if not sync:
        vis &= seg_q[:, None] == seg_k[None, :]
    return vis


def attend(q, k, v, pos, seg, *, sync: bool):
    """Softmax attention under :func:`visible`, in blocks of query rows.
    q (T, n_q, dh), k and v (T, n_kv, dh): query head j reads kv head
    j // (n_q // n_kv). Returns (T, n_q * dh)."""
    T, n_q, dh = q.shape
    n_kv = k.shape[1]
    q = q.reshape(T, n_kv, n_q // n_kv, dh)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo: lo + Q_BLOCK]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) * dh ** -0.5
        vis = visible(pos[lo: lo + Q_BLOCK], seg[lo: lo + Q_BLOCK], pos, seg, sync)
        s = jnp.where(vis, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(vis, p, 0.0)  # rows with nothing visible (padding)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI))
    return jnp.concatenate(outs, 0).reshape(T, n_q * dh)


@functools.partial(jax.jit, static_argnames=("dm",))
def _embed(table, tokens, *, dm: model.Dims):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32) * np.float32(dm.d ** 0.5)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(x, final_ln, table, *, dm: model.Dims, quant):
    """``table`` is the (d, Vp) head, or the (Vp, d) embedding when tied."""
    h = rms(x, final_ln, dm.eps)
    return mm(h, table.T if dm.tied else table, quant)[:, : dm.vocab]


def positions_and_segments(T: int, L: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Positions 0..T-1; the prompt's contiguous participant segments, then
    the publisher (participant n-1) for generated positions."""
    seg = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int32), segment_sizes(L, n)),
        np.full(T - L, n - 1, np.int32),
    ])
    return np.arange(T, dtype=np.int32), seg


def logits(w: dict, dm: model.Dims, tokens: np.ndarray, L: int, first: int, *, quant=None):
    """Float32 logits at positions first..T-1 of the sequence ``tokens``
    (T,), whose first L tokens are the prompt: a (rows, vocab) array whose
    first T - first rows are those positions (rows is that count rounded up
    to a multiple of ``PAD_OUT``; the rest are padding)."""
    layer = model.arch(dm.model_type).layer
    T = int(tokens.shape[0])
    Tp = -(-T // PAD_T) * PAD_T
    pos, seg = positions_and_segments(T, L, dm.participants)
    pos = np.concatenate([pos, np.arange(T, Tp, dtype=np.int32)])
    seg = np.concatenate([seg, np.full(Tp - T, -1, np.int32)])  # padding: no one's
    toks = np.concatenate([tokens.astype(np.int32), np.zeros(Tp - T, np.int32)])
    pos, seg = jnp.asarray(pos), jnp.asarray(seg)
    x = _embed(w["embed"], jnp.asarray(toks), dm=dm)
    for i in range(dm.n_layers):
        p, slot = divmod(i, dm.sync_interval)
        lw = {k: a[p] for k, a in w["layers"][slot].items()}
        x = layer(x, lw, pos, seg, dm=dm, index=i, quant=quant)
    n_out = T - first
    rows = -(-n_out // PAD_OUT) * PAD_OUT
    xs = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([x, jnp.zeros((rows, dm.d), x.dtype)]), first, rows)
    table = w["embed"] if dm.tied else w["head"]
    return _head(xs, w["final_ln"], table, dm=dm, quant=quant)
