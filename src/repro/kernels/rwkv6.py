"""Pallas TPU kernel: RWKV6 (Finch) WKV recurrence, chunked form.

TPU adaptation (DESIGN.md §6): the data-dependent-decay linear recurrence

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T),   S_t = diag(e^{w_t}) S_{t-1} + k_t v_t^T

is evaluated in chunks of CHUNK tokens so the MXU does the work:

  intra-chunk   A[t,i] = (r_t ⊙ e^{W_{t-1}}) · (k_i ⊙ e^{-W_i}),  i < t
                (W = inclusive cumsum of log-decay w within the chunk)
                + diagonal bonus A[t,t] = (r_t ⊙ u) · k_t
  inter-chunk   y += (r ⊙ e^{W_prev}) @ S
  state update  S ← diag(e^{W_C}) S + (k ⊙ e^{W_C - W})^T V

All exponents except ``e^{-W_i}`` are ≤ 0. With per-step log-decay clamped
to w ≥ -5 (the parameterization in models/ssm.py clamps, as common GLA/RWKV
chunked implementations do) and CHUNK = 16, ``-W_i ≤ 80`` keeps e^{-W}
inside float32 range; the A product itself is always ≤ O(1).

Grid = (B·H, n_chunks) with the chunk axis LAST (TPU grids iterate the last
axis sequentially), so the (dk, dv) state lives in VMEM scratch across
chunk steps. Validated against kernels/ref.py::rwkv6_ref in interpret
mode; the TPU compiler refuses its (1, CHUNK, 1, d) blocks, so ops.rwkv6
raises for the pallas backend on a TPU.

Validity/segment contract (kernels/core docstring, recurrence half):

* ``valid`` — 1-D ``(L,)`` or per-row 2-D ``(B, L)``; invalid tokens are
  gated on the host by the decay-masking rule (``w ← where(valid, w, 0)``,
  ``k ← where(valid, k, 0)``): decay ``e^0 = 1`` and a zero kv
  outer-product make the state update exact identity, so the chunked math
  is untouched and a pow2-padded suffix / ragged per-row batch never
  corrupts state.
* ``reset_mask`` — 1-D or per-row 2-D; runs IN the kernel. A reset before
  token t starts a new "epoch": the inclusive cumsum R of the reset flags
  partitions the chunk, the intra-chunk matrix is masked to same-epoch
  pairs (the decay weights e^{W_{t-1} - W_i} are already correct within an
  epoch — they only span post-reset tokens), the carried state contributes
  only to epoch-0 rows, and the state update keeps the S_prev term only
  when the chunk saw no reset and accumulates kv terms from the FINAL
  epoch only. Resets in earlier chunks are already baked into the carried
  scratch state, so each chunk is self-contained.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16
W_MIN = -5.0  # decay clamp (see module docstring)


def _kernel(
    r_ref,  # (1, CHUNK, 1, dk)
    k_ref,
    v_ref,  # (1, CHUNK, 1, dv)
    w_ref,  # (1, CHUNK, 1, dk) log-decay
    u_ref,  # (1, dk)
    reset_ref,  # (1, CHUNK) int32: 1 → zero the state before this step
    o_ref,  # (1, CHUNK, 1, dv)
    s_scr,  # (dk, dv) f32 state
    *,
    n_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, :, 0, :].astype(jnp.float32)  # (C, dk)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)  # (C, dv)
    w = jnp.maximum(w_ref[0, :, 0, :].astype(jnp.float32), W_MIN)
    u = u_ref[0, :].astype(jnp.float32)  # (dk,)
    R = jnp.cumsum(reset_ref[0], axis=0)  # (C,) inclusive epoch ids

    W = jnp.cumsum(w, axis=0)  # inclusive: W[t] = Σ_{j<=t} w_j
    W_prev = W - w  # exclusive:  Σ_{j<t} w_j
    W_total = W[-1]  # (dk,)

    r_dec = r * jnp.exp(W_prev)  # (C, dk)
    k_inv = k * jnp.exp(-W)  # bounded by CHUNK·|W_MIN| (see docstring)

    # strict-lower intra-chunk attention + u-bonus diagonal, restricted to
    # same-epoch (no reset in (i, t]) pairs
    A = jax.lax.dot_general(
        r_dec, k_inv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C): A[t, i]
    C = A.shape[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same_epoch = R[:, None] == R[None, :]
    A = jnp.where((t_idx > i_idx) & same_epoch, A, 0.0)
    diag = jnp.sum(r * u[None, :] * k, axis=-1)  # (C,)
    A = A + jnp.where(t_idx == i_idx, diag[:, None], 0.0)

    y = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # inter-chunk contribution from the carried state — epoch-0 rows only
    # (a reset anywhere before t cuts the carried state off)
    y_state = jax.lax.dot_general(
        r_dec, s_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y + jnp.where((R == 0)[:, None], y_state, 0.0)
    o_ref[0, :, 0, :] = y.astype(o_ref.dtype)

    # state update: S ← diag(e^{W_total}) S + (k ⊙ e^{W_total - W})^T @ V,
    # with the S term surviving only a reset-free chunk and kv terms taken
    # from the final epoch only (e^{W_total - W_i} spans only post-reset
    # tokens for i in the final epoch, so the weights stay correct)
    k_dec = k * jnp.exp(W_total[None, :] - W)
    k_dec = jnp.where((R == R[-1])[:, None], k_dec, 0.0)
    s_prev = jnp.where(R[-1] == 0, jnp.exp(W_total)[:, None] * s_scr[...], 0.0)
    s_scr[...] = s_prev + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def rwkv6_chunked(
    r: jnp.ndarray,  # (B, L, H, dk)
    k: jnp.ndarray,
    v: jnp.ndarray,  # (B, L, H, dv)
    w: jnp.ndarray,  # (B, L, H, dk) log-decay (<= 0)
    u: jnp.ndarray,  # (H, dk)
    *,
    reset_mask: Optional[jnp.ndarray] = None,  # (L,) or (B, L)
    valid: Optional[jnp.ndarray] = None,  # (L,) or (B, L)
    chunk: int = CHUNK,
    interpret: Optional[bool] = None,
):
    """Returns (y, final_state=None) from a zero initial state (state
    carries run the reference scan — ``ops.rwkv6`` decides); ``valid`` and
    per-row ``reset_mask`` run through the chunked kernel (module
    docstring)."""
    from repro.kernels.core import as_reset_rows, as_row_mask, pallas_interpret

    B, L, H, dk = r.shape
    dv = v.shape[-1]
    v2 = as_row_mask(valid, L)
    if v2 is not None:
        v4 = v2[..., None, None]
        w = jnp.where(v4, w, 0.0).astype(w.dtype)  # decay e^0 = 1
        k = jnp.where(v4, k, 0.0).astype(k.dtype)  # no state injection
    reset = as_reset_rows(reset_mask, B, L)
    pad = (-L) % chunk
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, w = z(r), z(k), z(v), z(w)
        reset = jnp.pad(reset, ((0, 0), (0, pad)))
    Lp = L + pad
    n_chunks = Lp // chunk

    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    grid = (B * H, n_chunks)

    def im4(bh, ci):
        return (bh // H, ci, bh % H, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, dk), im4),
            pl.BlockSpec((1, chunk, 1, dk), im4),
            pl.BlockSpec((1, chunk, 1, dv), im4),
            pl.BlockSpec((1, chunk, 1, dk), im4),
            pl.BlockSpec((1, dk), lambda bh, ci: (bh % H, 0)),
            pl.BlockSpec((1, chunk), lambda bh, ci: (bh // H, ci)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, dv), im4),
        out_shape=jax.ShapeDtypeStruct((B, Lp, H, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(r, k, v, w, u, reset)
    return out[:, :L], None
