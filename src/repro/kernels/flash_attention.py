"""Pallas TPU flash attention with FedAttn segment masking.

One kernel serves all three attention modes of the protocol:

  * Phase-I local attention  — ``local_only=True``: the segment-id block
    mask restricts each query to same-participant keys,
  * Phase-II global attention — no segment restriction (optionally a
    sparse-exchange ``contributed`` row mask),
  * sliding-window layers (gemma3) — relative-position window mask.

TPU adaptation (DESIGN.md §6): blockwise online-softmax with explicit
BlockSpec VMEM tiling. Q blocks of (BLOCK_Q, d_head) stream against KV
blocks of (BLOCK_K, d_head); the MXU sees (BLOCK_Q × d_head) @
(d_head × BLOCK_K) matmuls — both 128-aligned by construction. Heads ride
the lane axis of free ``(B, L, n_heads·d_head)`` reshapes, so each block's
trailing dims are whole tiles (the TPU compiler refuses a block whose last
two dims are neither 8/128-aligned nor the array's own). GQA is
expressed in the index_map (kv head = q head // q_per_kv), so KV tiles are
fetched once per group, not per query head. The m/l/acc running statistics
live in VMEM scratch across the KV-block grid dimension (TPU grids iterate
sequentially over the last axis, which makes the accumulation legal).

Vector contract: every position/segment/contribution vector may be shared
(1-D ``(L,)``) or per batch row (2-D ``(B, L)`` — continuous-batching
decode over a KV slot pool, coalesced admission prefill). Vectors are
normalized to ``(Bv, L)`` with ``Bv ∈ {1, B}`` and blocked as query
columns ``(1, block, 1)`` / key rows ``(1, 1, block)`` whose index map
selects row ``b`` when the vector is batched and row 0 when it is shared
— so batched calls cost no extra VMEM for shared vectors and the kernel
body is identical either way. The block mask itself is built by
:func:`repro.kernels.core.tile_visibility`, the rule body of the repo's
single mask constructor (sentinel conventions documented there).

Validated against kernels/ref.py in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, dtypes, mask modes, and batched
per-row vectors).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import core as _core

NEG_INF = _core.NEG_INF

BLOCK_Q = 128
BLOCK_K = 128


def _kernel(
    q_ref,  # (1, BLOCK_Q, dh) — head h's lane block of (B, Lq, nq·dh)
    k_ref,  # (1, BLOCK_K, dh) — kv head h // g
    v_ref,
    qpos_ref,  # (1, BLOCK_Q, 1) query column
    kpos_ref,  # (1, 1, BLOCK_K) key row
    qseg_ref,  # (1, BLOCK_Q, 1)
    kseg_ref,  # (1, 1, BLOCK_K)
    contrib_ref,  # (1, 1, BLOCK_K) int32 0/1
    o_ref,  # (1, BLOCK_Q, dh)
    m_scr,  # scratch (BLOCK_Q, 1) f32
    l_scr,
    acc_scr,  # (BLOCK_Q, dh) f32
    *,
    causal: bool,
    use_seg: bool,
    local_only: bool,
    use_contrib: bool,
    window: Optional[int],
    soft_cap: Optional[float],
    sm_scale: float,
    n_k_blocks: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale  # (BQ, dh)
    k = k_ref[0].astype(jnp.float32)  # (BK, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, BK)
    if soft_cap:
        s = jnp.tanh(s / soft_cap) * soft_cap

    # the block mask is the shared core's visibility rule on this tile's
    # query column / key row (negative kv segments are padding sentinels:
    # bucketed prefill pads with -1, this kernel's own block padding uses
    # -2 — never visible)
    mask = _core.tile_visibility(
        qpos_ref[0],
        kpos_ref[0],
        qseg_ref[0] if use_seg else None,
        kseg_ref[0] if use_seg else None,
        causal=causal,
        local_only=local_only,
        contributed=(contrib_ref[0] > 0) if use_contrib else None,
        window=window,
    )  # (BQ, BK)

    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _vec_block(vec: jnp.ndarray, block: int, axis: str) -> pl.BlockSpec:
    """BlockSpec of a (Bv, L, 1) query column or (Bv, 1, L) key row: one
    block per (q or k) grid step whose batch index follows the batch grid
    axis when the vector is per-row (Bv > 1) and pins row 0 when it is
    shared (Bv == 1). Trailing block dims are whole or 8/128-aligned."""
    batched = vec.shape[0] > 1
    if axis == "q":
        return pl.BlockSpec(
            (1, block, 1),
            lambda b, h, qi, ki, _bt=batched: (b if _bt else 0, qi, 0),
        )
    return pl.BlockSpec(
        (1, 1, block), lambda b, h, qi, ki, _bt=batched: (b if _bt else 0, 0, ki)
    )


def flash_attention(
    q: jnp.ndarray,  # (B, Lq, nq, dh)
    k: jnp.ndarray,  # (B, Lk, nkv, dh)
    v: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (Lq,) or (B, Lq)
    kv_pos: jnp.ndarray,  # (Lk,) or (B, Lk)
    q_seg: Optional[jnp.ndarray] = None,  # (Lq,) or (B, Lq)
    kv_seg: Optional[jnp.ndarray] = None,  # (Lk,) or (B, Lk)
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,  # (Lk,) or (B, Lk)
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Blocked FedAttn attention (module docstring). ``interpret=None``
    follows :func:`repro.kernels.core.pallas_interpret`. Compiled, the head
    dim must be a multiple of 128 (one lane block per head)."""
    B, Lq, nq, dh = q.shape
    _, Lk, nkv, _ = k.shape
    assert nq % nkv == 0
    g = nq // nkv
    scale = sm_scale if sm_scale is not None else dh**-0.5
    interpret = _core.pallas_interpret(interpret)

    # normalize every vector to (Bv, L), Bv ∈ {1, B} (shared vs per-row)
    as2 = lambda a: None if a is None else (a if a.ndim == 2 else a[None])
    q_pos, kv_pos = as2(q_pos), as2(kv_pos)
    q_seg, kv_seg, contributed = as2(q_seg), as2(kv_seg), as2(contributed)
    for name, vec, L in (
        ("q_pos", q_pos, Lq), ("kv_pos", kv_pos, Lk), ("q_seg", q_seg, Lq),
        ("kv_seg", kv_seg, Lk), ("contributed", contributed, Lk),
    ):
        assert vec is None or (vec.shape[0] in (1, B) and vec.shape[1] == L), (
            f"{name}: expected ({{1,{B}}}, {L}), got {vec.shape}"
        )

    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    # pad sequences to block multiples; padded kv rows carry sentinel pos
    pad_q = (-Lq) % block_q
    pad_k = (-Lk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
        if q_seg is not None:
            q_seg = jnp.pad(
                q_seg, ((0, 0), (0, pad_q)), constant_values=_core.PAD_SEGMENT
            )
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(
            kv_pos, ((0, 0), (0, pad_k)), constant_values=_core.PAD_POS
        )
        if kv_seg is not None:
            kv_seg = jnp.pad(
                kv_seg, ((0, 0), (0, pad_k)),
                constant_values=_core.KERNEL_PAD_SEGMENT,
            )
        if contributed is not None:
            contributed = jnp.pad(
                contributed, ((0, 0), (0, pad_k)), constant_values=False
            )
    Lq_p, Lk_p = Lq + pad_q, Lk + pad_k
    n_q_blocks = Lq_p // block_q
    n_k_blocks = Lk_p // block_k

    use_seg = q_seg is not None and kv_seg is not None
    if not use_seg:
        q_seg = jnp.zeros((1, Lq_p), jnp.int32)
        kv_seg = jnp.zeros((1, Lk_p), jnp.int32)
    use_contrib = contributed is not None and not local_only and use_seg
    contrib = (
        contributed.astype(jnp.int32)
        if use_contrib
        else jnp.ones((1, Lk_p), jnp.int32)
    )
    # query-side vectors as columns, key-side vectors as rows
    col = lambda a: a.astype(jnp.int32)[:, :, None]
    row = lambda a: a.astype(jnp.int32)[:, None, :]
    q_pos, q_seg = col(q_pos), col(q_seg)
    kv_pos, kv_seg, contrib = row(kv_pos), row(kv_seg), row(contrib)

    kernel = functools.partial(
        _kernel,
        causal=causal,
        use_seg=use_seg,
        local_only=local_only and use_seg,
        use_contrib=use_contrib,
        window=window,
        soft_cap=soft_cap,
        sm_scale=scale,
        n_k_blocks=n_k_blocks,
    )

    # heads ride the lane axis: (B, L, n·dh) views, one dh-wide lane block
    # per head (GQA: q head h reads kv head h // g in the index map, so KV
    # tiles are fetched once per group)
    grid = (B, nq, n_q_blocks, n_k_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, h, qi, ki: (b, qi, h)),
            pl.BlockSpec((1, block_k, dh), lambda b, h, qi, ki: (b, ki, h // g)),
            pl.BlockSpec((1, block_k, dh), lambda b, h, qi, ki: (b, ki, h // g)),
            _vec_block(q_pos, block_q, "q"),
            _vec_block(kv_pos, block_k, "k"),
            _vec_block(q_seg, block_q, "q"),
            _vec_block(kv_seg, block_k, "k"),
            _vec_block(contrib, block_k, "k"),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, h, qi, ki: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, Lq_p, nq * dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(
        q.reshape(B, Lq_p, nq * dh), k.reshape(B, Lk_p, nkv * dh),
        v.reshape(B, Lk_p, nkv * dh), q_pos, kv_pos, q_seg, kv_seg, contrib,
    )
    return out.reshape(B, Lq_p, nq, dh)[:, :Lq]
