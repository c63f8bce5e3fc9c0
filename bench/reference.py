"""The plain reference: a float32 FedAttn forward of a qwen2-family model.

Written from the published architecture (Qwen2: RMSNorm, RoPE, GQA with
q/k/v bias, SwiGLU, tied or untied head) and the FedAttn visibility rule
(``bench/flops.py`` states it), in plain ``jax.numpy`` with every matrix
product at ``precision=HIGHEST``. It imports nothing of the program and
reads only the benchmark's own weights (``bench/model.py`` layout).

One departure from Qwen2 follows the program: embedded tokens are scaled
by sqrt(hidden_size) (see the configuration files' ``assumed``).

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

from bench.flops import segment_sizes
from bench.model import Dims

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


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
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
    dh, g = dm.d_head, dm.n_q // dm.n_kv
    h = _rms(x, lw["ln1"], dm.eps)
    q = (_mm(h, lw["wq"], quant) + lw["bq"].astype(jnp.float32)).reshape(T, dm.n_q, dh)
    k = (_mm(h, lw["wk"], quant) + lw["bk"].astype(jnp.float32)).reshape(T, dm.n_kv, dh)
    v = (_mm(h, lw["wv"], quant) + lw["bv"].astype(jnp.float32)).reshape(T, dm.n_kv, dh)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    q = q.reshape(T, dm.n_kv, g, dh)  # query head j reads kv head j // g
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo: lo + Q_BLOCK]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) * dh ** -0.5
        vis = pos[lo: lo + Q_BLOCK, None] >= pos[None, :]
        vis &= seg[None, :] >= 0
        if not sync:
            vis &= seg[lo: lo + Q_BLOCK, None] == seg[None, :]
        s = jnp.where(vis, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(vis, p, 0.0)  # rows with nothing visible (padding)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, dm.n_q * dh)
    x = x + _mm(o, lw["wo"], quant)
    h = _rms(x, lw["ln2"], dm.eps)
    f = jax.nn.silu(_mm(h, lw["wg"], quant)) * _mm(h, lw["wu"], quant)
    return x + _mm(f, lw["wd"], quant)


@functools.partial(jax.jit, static_argnames=("dm",))
def _embed(table, tokens, *, dm: Dims):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32) * np.float32(dm.d ** 0.5)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(x, final_ln, table, *, dm: Dims, quant):
    """``table`` is the (d, Vp) head, or the (Vp, d) embedding when tied."""
    h = _rms(x, final_ln, dm.eps)
    return _mm(h, table.T if dm.tied else table, quant)[:, : dm.vocab]


def positions_and_segments(T: int, L: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Positions 0..T-1; the prompt's contiguous participant segments, then
    the publisher (participant n-1) for generated positions."""
    seg = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int32), segment_sizes(L, n)),
        np.full(T - L, n - 1, np.int32),
    ])
    return np.arange(T, dtype=np.int32), seg


def logits(w: dict, dm: Dims, tokens: np.ndarray, L: int, first: int, *, quant=None):
    """Float32 logits at positions first..T-1 of the sequence ``tokens``
    (T,), whose first L tokens are the prompt: a (rows, vocab) array whose
    first T - first rows are those positions (rows is that count rounded up
    to a multiple of ``PAD_OUT``; the rest are padding)."""
    T = int(tokens.shape[0])
    Tp = -(-T // PAD_T) * PAD_T
    pos, seg = positions_and_segments(T, L, dm.participants)
    pos = np.concatenate([pos, np.arange(T, Tp, dtype=np.int32)])
    seg = np.concatenate([seg, np.full(Tp - T, -1, np.int32)])  # padding: no one's
    toks = np.concatenate([tokens.astype(np.int32), np.zeros(Tp - T, np.int32)])
    pos, seg = jnp.asarray(pos), jnp.asarray(seg)
    x = _embed(w["embed"], jnp.asarray(toks), dm=dm)
    for layer in range(dm.n_layers):
        p, i = divmod(layer, dm.sync_interval)
        lw = {k: a[p] for k, a in w["layers"][i].items()}
        x = _layer(x, lw, pos, seg, dm=dm, sync=dm.is_sync(layer), quant=quant)
    n_out = T - first
    rows = -(-n_out // PAD_OUT) * PAD_OUT
    xs = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([x, jnp.zeros((rows, dm.d), x.dtype)]), first, rows)
    table = w["embed"] if dm.tied else w["head"]
    return _head(xs, w["final_ln"], table, dm=dm, quant=quant)
