"""Pallas TPU kernel: Mamba1 selective scan, chunked with VMEM state.

The selective-scan recurrence

    h_t = exp(Δ_t ⊙ A) h_{t-1} + (Δ_t x_t) B_t^T,   y_t = h_t C_t + D ⊙ x_t

has per-(channel, state) decay ``exp(Δ_t[d]·A[d,s])`` — NOT separable into
a matmul form like WKV6 (the exponent depends on both d and s through the
data-dependent Δ). We therefore keep the faithful sequential structure but
block it for the TPU memory hierarchy: channels are tiled into
(BLOCK_D, d_state) VMEM-resident state slabs, the time axis is chunked, and
the inner ``fori_loop`` performs CHUNK vectorized state updates per grid
step entirely out of VMEM/VREGs (this mirrors how the original CUDA kernel
keeps h in registers/SRAM — the TPU analogue is VMEM residency, DESIGN.md
hardware-adaptation note).

Grid = (B, n_d_blocks, n_chunks), chunk axis LAST (sequential on TPU) so
the state scratch carries across chunks. Validated against
kernels/ref.py::mamba_scan_ref in interpret mode; the TPU compiler refuses
its per-row (1, CHUNK) reset blocks, so ops.mamba_scan raises for the
pallas backend on a TPU.

Validity/segment contract (kernels/core docstring, recurrence half):

* ``valid`` — 1-D ``(L,)`` or per-row 2-D ``(B, L)``; invalid tokens are
  gated on the host by the Δ·mask rule (``Δ ← where(valid, Δ, 0)``), which
  makes their in-kernel state update exact identity (decay ``exp(0·A)=1``,
  zero injection) with NO kernel change — the kernel scans a pow2-padded
  suffix or a ragged per-row batch without corrupting state.
* ``reset_mask`` — 1-D or per-row 2-D; runs IN the kernel (a reset input
  block zeroes the VMEM state slab before the flagged step), so
  FedAttn-local segment scans no longer fall back to the oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 32
BLOCK_D = 256


def _kernel(
    x_ref,  # (1, CHUNK, BLOCK_D)
    dt_ref,  # (1, CHUNK, BLOCK_D)
    A_ref,  # (BLOCK_D, ds)
    B_ref,  # (1, CHUNK, ds)
    C_ref,  # (1, CHUNK, ds)
    D_ref,  # (BLOCK_D,)
    reset_ref,  # (1, CHUNK) int32: 1 → zero the state before this step
    o_ref,  # (1, CHUNK, BLOCK_D)
    h_scr,  # (BLOCK_D, ds) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (C, D)
    dt = dt_ref[0].astype(jnp.float32)
    A = A_ref[...].astype(jnp.float32)  # (D, ds)
    Bm = B_ref[0].astype(jnp.float32)  # (C, ds)
    Cm = C_ref[0].astype(jnp.float32)
    D = D_ref[...].astype(jnp.float32)  # (D,)
    reset = reset_ref[0]  # (C,) int32

    def step(t, carry):
        h, ys = carry
        h = jnp.where(reset[t] > 0, jnp.zeros_like(h), h)
        decay = jnp.exp(dt[t][:, None] * A)  # (D, ds)
        h = decay * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        y_t = jnp.sum(h * Cm[t][None, :], axis=-1) + D * x[t]
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, axis=0)
        return h, ys

    ys0 = jnp.zeros(x.shape, jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (h_scr[...], ys0))
    h_scr[...] = h
    o_ref[0] = ys.astype(o_ref.dtype)


def mamba_scan_chunked(
    x: jnp.ndarray,  # (B, L, d_in)
    delta: jnp.ndarray,  # (B, L, d_in)
    A: jnp.ndarray,  # (d_in, ds)
    Bm: jnp.ndarray,  # (B, L, ds)
    C: jnp.ndarray,  # (B, L, ds)
    D: jnp.ndarray,  # (d_in,)
    *,
    reset_mask: Optional[jnp.ndarray] = None,  # (L,) or (B, L)
    valid: Optional[jnp.ndarray] = None,  # (L,) or (B, L)
    chunk: int = CHUNK,
    block_d: int = BLOCK_D,
    interpret: Optional[bool] = None,
):
    """Returns (y, final_state=None) from a zero initial state (state
    carries run the oracle — ``ops.mamba_scan`` decides); ``valid`` and
    per-row ``reset_mask`` run through the kernel (module docstring)."""
    from repro.kernels.core import as_reset_rows, as_row_mask, pallas_interpret

    B, L, d_in = x.shape
    v2 = as_row_mask(valid, L)
    if v2 is not None:
        delta = jnp.where(v2[..., None], delta, 0.0).astype(delta.dtype)
    reset = as_reset_rows(reset_mask, B, L)
    ds = A.shape[-1]
    block_d = min(block_d, d_in)
    pad_t = (-L) % chunk
    pad_d = (-d_in) % block_d
    if pad_t or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_d)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_t), (0, pad_d)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_t), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad_t), (0, 0)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
        D = jnp.pad(D, (0, pad_d))
        reset = jnp.pad(reset, ((0, 0), (0, pad_t)))
    Lp, Dp = L + pad_t, d_in + pad_d
    n_chunks = Lp // chunk
    n_d_blocks = Dp // block_d

    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_d_blocks, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((block_d, ds), lambda b, di, ci: (di, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((block_d,), lambda b, di, ci: (di,)),
            pl.BlockSpec((1, chunk), lambda b, di, ci: (b, ci)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
        out_shape=jax.ShapeDtypeStruct((B, Lp, Dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x, delta, A, Bm, C, D, reset)
    return out[:, :L, :d_in], None
