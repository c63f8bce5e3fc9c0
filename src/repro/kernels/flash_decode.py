"""Fused Pallas paged flash-decode: ONE kernel for the resident pooled step.

The pooled decode step used to read KV through XLA gathers that densify a
slot's pages into a ``(B, capacity)`` transient (or chunk-stream page
groups) before the shared softmax body ran — and quantized pools
dequantized *outside* the kernel, spending part of the memory traffic the
int8/fp8 codes saved. This module fuses the whole read side:

* **In-kernel page loads.** The grid is ``(B, nkv, P')`` — one program per
  (slot, kv head, page-table entry); the ``g`` query heads of a GQA group
  and the ``S`` query rows share one program as ``g·S`` rows of a single
  tile. The page table rides in as a scalar-prefetch operand and the K/V
  BlockSpec *index maps* resolve each program's physical page
  (``min(table[b, i], N-1)``), reading the ``(page_size, dh)`` block of
  kv head ``h`` straight from the pool viewed as ``(num_pages,
  page_size, nkv·dh)`` (a free reshape — every block keeps whole
  trailing dims, as the TPU's tiling requires). The dense ``(B,
  capacity)`` cache is never materialized — the fused jaxpr contains no
  full-pool gather (audited: ``analysis.jaxpr_audit.audit_fused_decode``).
* **Split-KV flash-decoding.** Each program emits partial ``(m, l, acc)``
  softmax stats in the exact ``kernels.core.masked_attention(
  return_stats=True)`` vocabulary; :func:`_finish` reduces them with the
  same max/exp-correction/sum combine ``distributed/spmd_attention``
  already uses across shards. Under SPMD the paged decode therefore
  becomes shard-local-kernel + the existing ``pmax``/``psum`` collective
  combine — no new distributed math.
* **In-kernel dequant.** A quantized pool's ``sk``/``sv`` scale leaves
  ride in as extra operands, block-indexed by the same resolved page; the
  codes dequantize at load via ``serving.quant.dequantize`` (scale
  *arithmetic* stays in the quant module — the kernel only applies
  ``code * scale``), so everything downstream of the load is the dense
  f32 contract.
* **The full core visibility vocabulary.** 2-D per-row pos/seg blocks,
  sentinel-page columns forced to ``PAD_POS``/``KERNEL_PAD_SEGMENT``
  *before* any visibility decision (visibility is never decided by page
  identity), ``window``/``soft_cap``/GQA (``q`` head ``h`` reads kv head
  ``h // g`` — the head order of ``kernels.core.grouped_query``), ``contributed``
  sparse-exchange thinning and ``publisher_lo``. ``S > 1`` rows are the
  multi-query verify form, so speculative decode rides the same kernel.

Numerics: split-KV softmax is mathematically exact but associates
differently from the one-shot dense softmax, so outputs agree with the
gather path to f32 rounding (logprobs ~1e-5; greedy tokens exact on the
pinned scheduler traces — the documented tolerance). Bitwise parity is
pinned against :func:`paged_flash_decode_ref` — a pure-XLA twin with the
IDENTICAL per-page partition (both run :func:`_block_attend` on the same
operands and share :func:`_finish`). One exception: under ``soft_cap`` the
backend's ``tanh`` wobbles at 1 ulp with vectorization shape, so
soft-capped parity is to f32 rounding rather than bitwise.

``interpret=None`` follows :func:`repro.kernels.core.pallas_interpret`:
compiled on a TPU, the JAX interpreter elsewhere (CI runs the kernel body
interpreted — bitwise-testable on CPU).

Mass (the ``'attnmass'`` wiring): with ``return_mass`` the kernel also
emits each page block's masked softmax numerators; :func:`_finish`
rebases them to the combined max (``p_rel``) and — in the non-stats form
— returns ``sum_{head,row}(p_rel / l)``: each column's normalized
attention probability mass, shape ``(B, capacity)``. With
``return_stats`` the raw ``p_rel`` (relative to the returned ``m``) comes
back instead so the SPMD combine can apply its global correction before
reducing.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import core as _core


def _block_attend(q, k, v, mask, soft_cap):
    """Attention stats of one (query rows × KV page block) tile.

    ``q`` (R, dh) **pre-scaled** f32 — the ``R = g·S`` query rows of one
    GQA group, ``k``/``v`` (ps, dh) f32 (already dequantized), ``mask``
    (R, ps) bool. Returns ``(m, l, acc, p)`` — ``m``/``l`` (R, 1) columns,
    ``acc`` (R, dh), ``p`` (R, ps) the masked softmax numerators relative
    to ``m``. The ONE tile body: the Pallas kernel and the XLA ref twin
    both run exactly this function on identical operands, which is what
    makes their parity bitwise. Fully-masked rows follow the core contract
    (masked_attention): ``p`` is re-masked to zero, so they contribute
    ``l = 0`` and combine to zero output, never NaN."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (R, ps)
    if soft_cap:
        s = jnp.tanh(s / soft_cap) * soft_cap
    s = jnp.where(mask, s, _core.NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (R, 1)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)  # (R, ps)
    l = jnp.sum(p, axis=-1, keepdims=True)  # (R, 1)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (R, dh)
    return m, l, acc, p


def _prep(q, pk, pages, q_pos, kv_pos, q_seg, kv_seg, contributed, local_only):
    """Shared operand pre-pass, in the tile layout both implementations
    read. Queries are grouped by kv head: ``qg`` (B, nkv, g·S, dh) with
    row ``j·S + s`` = q head ``h·g + j`` at query row ``s`` (the head
    order of ``kernels.core.grouped_query``). Query-side vectors become per-group
    columns ``(B, g·S, 1)``; key-side vectors become per-page rows
    ``(B, P', 1, ps)``, with sentinel-page columns (table entries >=
    num_pages) forced behind the ``PAD_POS``/``KERNEL_PAD_SEGMENT``
    sentinels BEFORE any visibility decision — gathers/block loads clamp,
    masks hide (the kernels.core paged contract). Every block of these
    layouts has whole trailing dims, as the TPU's tiling requires."""
    B, S, nq, dh = q.shape
    N, ps, nkv = pk.shape[0], pk.shape[1], pk.shape[2]
    g = nq // nkv
    Pp = pages.shape[1]
    Lk = Pp * ps
    pages = pages.astype(jnp.int32)
    qg = q.reshape(B, S, nkv, g, dh).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(B, nkv, g * S, dh)
    col_valid = jnp.repeat(pages < N, ps, axis=1)  # (B, Lk)
    q_col = lambda a: jnp.tile(
        jnp.broadcast_to(jnp.atleast_2d(a), (B, S)), (1, g)
    )[:, :, None]
    kv_row = lambda a, pad: jnp.where(
        col_valid, jnp.broadcast_to(jnp.atleast_2d(a), (B, Lk)), pad
    ).astype(jnp.int32).reshape(B, Pp, 1, ps)
    qp = q_col(q_pos)
    kp = kv_row(kv_pos, _core.PAD_POS)
    qs = ks = ct = None
    if q_seg is not None and kv_seg is not None:
        qs = q_col(q_seg)
        ks = kv_row(kv_seg, _core.KERNEL_PAD_SEGMENT)
        if not local_only and contributed is not None:
            # int32 0/1: bool blocks are fragile
            ct = kv_row(jnp.asarray(contributed).astype(jnp.int32), 0)
    return qg, pages, qp, kp, qs, ks, ct


def _ungroup(x, S):
    """(B, nkv, P', g·S, T) tile-layout stats → (B, nq, P', S, T)."""
    B, nkv, Pp, R, T = x.shape
    g = R // S
    x = x.reshape(B, nkv, Pp, g, S, T).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, nkv * g, Pp, S, T)


def _finish(q_dtype, S, m_p, l_p, acc_p, p_p, *, return_stats, return_mass):
    """Combine per-page partial stats — THE split-KV reduction, in the
    exact stats vocabulary of ``core.masked_attention(return_stats=True)``
    / the spmd_attention pmax-psum combine: global max over page groups,
    exp-correction of each group's ``l``/``acc``, sum. Shared by the fused
    kernel and the XLA ref twin (bitwise parity); takes their tile-layout
    outputs (B, nkv, P', g·S, ·)."""
    m_p, l_p = _ungroup(m_p, S)[..., 0], _ungroup(l_p, S)[..., 0]
    acc_p = _ungroup(acc_p, S)
    B, nq, Pp, S = m_p.shape
    m_g = jnp.max(m_p, axis=2)  # (B, nq, S)
    corr = jnp.exp(m_p - m_g[:, :, None, :])  # (B, nq, P', S)
    l_g = jnp.sum(l_p * corr, axis=2)
    acc_g = jnp.sum(acc_p * corr[..., None], axis=2)  # (B, nq, S, dh)
    p_rel = None
    if p_p is not None:
        p_p = _ungroup(p_p, S)
        ps = p_p.shape[-1]
        # numerators rebased to the combined max, page blocks → columns
        p_rel = (p_p * corr[..., None]).transpose(0, 1, 3, 2, 4).reshape(
            B, nq, S, Pp * ps
        )
    if return_stats:
        acc_out = acc_g.transpose(0, 2, 1, 3)  # (B, S, nq, dh)
        if return_mass:
            return m_g, l_g, acc_out, p_rel
        return m_g, l_g, acc_out
    denom = jnp.maximum(l_g, 1e-20)
    out = (acc_g / denom[..., None]).transpose(0, 2, 1, 3).astype(q_dtype)
    if return_mass:
        mass = jnp.sum(p_rel / denom[..., None], axis=(1, 2))  # (B, Lk)
        return out, mass
    return out


def paged_flash_decode(
    q: jnp.ndarray,  # (B, S, nq, dh) — S=1 decode or S=k+1 verify rows
    pk: jnp.ndarray,  # (num_pages, page_size, nkv, dh) physical pool
    pv: jnp.ndarray,
    pages: jnp.ndarray,  # (B, P') int32 tables; entries >= num_pages = holes
    *,
    q_pos: jnp.ndarray,  # (S,) or (B, S)
    kv_pos: jnp.ndarray,  # (P'*ps,) or (B, P'*ps)
    q_seg: Optional[jnp.ndarray] = None,
    kv_seg: Optional[jnp.ndarray] = None,
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    publisher_lo: Optional[int] = None,  # static int (never traced)
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, nkv) f32 — quant pool
    v_scales: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
    return_mass: bool = False,
    interpret: Optional[bool] = None,
):
    """The fused paged flash-decode entry point (module docstring).

    Returns the normalized ``(B, S, nq, dh)`` output; with ``return_stats``
    the combinable ``(m, l, acc)`` stats instead (SPMD shard-local form);
    ``return_mass`` appends the per-column softmax mass ``(B, P'*ps)``
    (stats form: the raw ``p_rel`` numerators ``(B, nq, S, P'*ps)``).
    Compiled, the head dim must be a multiple of 128 (one lane block per
    kv head)."""
    B, S, nq, dh = q.shape
    N, ps, nkv, _ = pk.shape
    Pp = pages.shape[1]
    R = (nq // nkv) * S
    scale = sm_scale if sm_scale is not None else dh**-0.5
    quant = k_scales is not None
    interpret = _core.pallas_interpret(interpret)

    qg, pages, qp, kp, qs, ks, ct = _prep(
        q, pk, pages, q_pos, kv_pos, q_seg, kv_seg, contributed, local_only
    )
    use_seg = qs is not None
    use_ct = ct is not None

    # index maps: grid (b, h, pi) over (slot, kv head, page-table entry)
    # + the scalar-prefetched page table `pr`; they return BLOCK indices —
    # the resolved (clamped) physical page for pool-shaped operands. The
    # pool is viewed as (N, ps, nkv·dh) so kv head h is lane block h.
    pg_of = lambda b, pi, pr: jnp.minimum(pr[b, pi], N - 1)
    pool_spec = pl.BlockSpec(
        (1, ps, dh), lambda b, h, pi, pr: (pg_of(b, pi, pr), 0, h)
    )
    q_rows = pl.BlockSpec((1, R, 1), lambda b, h, pi, pr: (b, 0, 0))
    kv_cols = pl.BlockSpec((1, 1, 1, ps), lambda b, h, pi, pr: (b, pi, 0, 0))

    in_specs = [
        pl.BlockSpec((1, 1, R, dh), lambda b, h, pi, pr: (b, h, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [qg, pk.reshape(N, ps, nkv * dh), pv.reshape(N, ps, nkv * dh)]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, 1, 1), lambda b, h, pi, pr: (pg_of(b, pi, pr), h, 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales.reshape(N, nkv, 1, 1),
                     v_scales.reshape(N, nkv, 1, 1)]
    in_specs += [q_rows, kv_cols]
    operands += [qp, kp]
    if use_seg:
        in_specs += [q_rows, kv_cols]
        operands += [qs, ks]
    if use_ct:
        in_specs += [kv_cols]
        operands += [ct]

    tile = lambda w: pl.BlockSpec(
        (1, 1, 1, R, w), lambda b, h, pi, pr: (b, h, pi, 0, 0)
    )
    stat = lambda w: jax.ShapeDtypeStruct((B, nkv, Pp, R, w), jnp.float32)
    out_specs = [tile(1), tile(1), tile(dh)]
    out_shape = [stat(1), stat(1), stat(dh)]
    if return_mass:
        out_specs.append(tile(ps))
        out_shape.append(stat(ps))

    def kernel(pages_ref, *refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        sk_ref = next(it) if quant else None
        sv_ref = next(it) if quant else None
        qp_ref, kp_ref = next(it), next(it)
        qs_ref = next(it) if use_seg else None
        ks_ref = next(it) if use_seg else None
        ct_ref = next(it) if use_ct else None
        m_ref, l_ref, acc_ref = next(it), next(it), next(it)
        mass_ref = next(it) if return_mass else None

        qv = q_ref[0, 0].astype(jnp.float32) * scale  # (R, dh)
        kv = k_ref[0]  # (ps, dh) codes or dense
        vv = v_ref[0]
        if quant:
            # dequant-at-load: the codec semantics live in serving/quant —
            # this kernel only applies the (already per-page-per-head
            # resolved) scale to its block
            from repro.serving import quant as _quant

            kv = _quant.dequantize(kv, sk_ref[0, 0, 0])
            vv = _quant.dequantize(vv, sv_ref[0, 0, 0])
        else:
            kv = kv.astype(jnp.float32)
            vv = vv.astype(jnp.float32)
        mask = _core.tile_visibility(
            qp_ref[0], kp_ref[0, 0],
            qs_ref[0] if use_seg else None,
            ks_ref[0, 0] if use_seg else None,
            causal=causal, local_only=local_only,
            contributed=(ct_ref[0, 0] != 0) if use_ct else None,
            window=window, publisher_lo=publisher_lo,
        )  # (R, ps)
        m, l, acc, p = _block_attend(qv, kv, vv, mask, soft_cap)
        m_ref[0, 0, 0] = m
        l_ref[0, 0, 0] = l
        acc_ref[0, 0, 0] = acc
        if return_mass:
            mass_ref[0, 0, 0] = p

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nkv, Pp),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="flash_decode",
    )(pages, *operands)
    p_p = outs[3] if return_mass else None
    return _finish(
        q.dtype, S, outs[0], outs[1], outs[2], p_p,
        return_stats=return_stats, return_mass=return_mass,
    )


def paged_flash_decode_ref(
    q: jnp.ndarray,
    pk: jnp.ndarray,
    pv: jnp.ndarray,
    pages: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    q_seg: Optional[jnp.ndarray] = None,
    kv_seg: Optional[jnp.ndarray] = None,
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    publisher_lo: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
    return_mass: bool = False,
):
    """Pure-XLA twin of :func:`paged_flash_decode` with the IDENTICAL
    per-page partition and combine: gathers each table entry's (clamped)
    page block, maps :func:`_block_attend` over every (B, kv head, page)
    tile on the same grouped query rows and reduces through the shared
    :func:`_finish` — the bitwise parity target for the interpret-mode
    kernel (tests/test_flash_decode.py)."""
    B, S, nq, dh = q.shape
    N, ps, nkv, _ = pk.shape
    scale = sm_scale if sm_scale is not None else dh**-0.5

    qg, pages, qp, kp, qs, ks, ct = _prep(
        q, pk, pages, q_pos, kv_pos, q_seg, kv_seg, contributed, local_only
    )
    # (B, P', R, ps): query columns (B, 1, R, 1) against page rows
    mask = _core.tile_visibility(
        qp[:, None], kp,
        qs[:, None] if qs is not None else None, ks,
        causal=causal, local_only=local_only,
        contributed=None if ct is None else ct != 0,
        window=window, publisher_lo=publisher_lo,
    )

    idx = jnp.minimum(pages, N - 1)
    kb = jnp.take(pk, idx, axis=0)  # (B, P', ps, nkv, dh)
    vb = jnp.take(pv, idx, axis=0)
    if k_scales is not None:
        from repro.serving import quant as _quant

        kb = _quant.dequantize(kb, jnp.take(k_scales, idx, axis=0)[:, :, None, :])
        vb = _quant.dequantize(vb, jnp.take(v_scales, idx, axis=0)[:, :, None, :])
    else:
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
    Pp, R = pages.shape[1], qg.shape[2]
    # one (kv head, page) tile per map step, flattened over (B, nkv, P'):
    # each step runs the kernel's unbatched tile body on identical operands
    kt = kb.transpose(0, 3, 1, 2, 4).reshape(-1, ps, dh)
    vt = vb.transpose(0, 3, 1, 2, 4).reshape(-1, ps, dh)
    qt = jnp.broadcast_to(
        (qg.astype(jnp.float32) * scale)[:, :, None], (B, nkv, Pp, R, dh)
    ).reshape(-1, R, dh)
    mt = jnp.broadcast_to(mask[:, None], (B, nkv, Pp, R, ps)).reshape(-1, R, ps)
    stats = jax.lax.map(lambda t: _block_attend(*t, soft_cap), (qt, kt, vt, mt))
    m_p, l_p, acc_p, p_p = (
        x.reshape((B, nkv, Pp) + x.shape[1:]) for x in stats
    )
    return _finish(
        q.dtype, S, m_p, l_p, acc_p, p_p if return_mass else None,
        return_stats=return_stats, return_mass=return_mass,
    )
