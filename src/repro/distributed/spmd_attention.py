"""SPMD FedAttn attention: participants = sequence shards on the seq axis.

This is the TPU-native realization of the paper's protocol (DESIGN.md §2):

  * **Phase I (local layers)** — each shard runs flash attention over its
    own (Q, K, V) slice. ZERO collectives: the HLO of a local layer
    contains no all-gather/all-reduce on the sequence axis. This is the
    communication saving the paper trades quality for.
  * **Phase II (sync layers)** — ``lax.all_gather`` of (K, V[, positions])
    over the seq axis (eq. 20: KV exchange + concat aggregation), then
    local-Q × global-KV flash attention (eq. 21).
  * **Sparse KV exchange** (eq. 37) — each shard top-k-selects
    ``ratio · L_shard`` KV rows *before* the gather, shrinking collective
    bytes by the ratio; local queries keep their full local KV view
    (gathered own-shard rows are invalidated by a position sentinel to
    avoid double counting).
  * **Decode** — flash-decoding-style: each shard computes partial softmax
    statistics over its cache slice (the shared core's
    ``masked_attention(..., return_stats=True)``); a pmax/psum over the
    cache axes combines them exactly. Masking comes from the same
    ``kernels.core.visibility`` every other path uses — either the
    per-row segment vectors (continuous-batching slot pools: q/kv vectors
    may be 2-D ``(B, ·)``), or the ``publisher_lo`` position rule when no
    segments are available.

All masks and softmax bodies here are the shared attention core's
(:mod:`repro.kernels.core`) — this module contains only the collectives
and the shard bookkeeping around them.

Partitions must be contiguous-equal (participant n == shard n); segment ids
are derived arithmetically from positions.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.distributed import runtime
from repro.kernels import core as K

NEG_INF = K.NEG_INF


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill_attention(
    q: jnp.ndarray,  # (B, L, nq, dh) — L sharded over seq axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (L,) global positions, sharded over seq axis
    causal: bool,
    sync: bool,
    window: Optional[int] = None,
    exchange_ratio: float = 1.0,
    kv_selection: str = "strided",
    kv_quant: str = "none",
    attn_mass: Optional[jnp.ndarray] = None,  # (L,) sharded, 'attnmass' stats
    rng: Optional[jnp.ndarray] = None,  # PRNG key for 'random' selection
    round_index: int = 0,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    ctx = runtime.current()
    assert ctx is not None, "SPMD attention requires an active SpmdContext"
    mesh, ax = ctx.mesh, ctx.seq_axis
    bspec = P(ctx.bfirst, ax, None, None)
    from repro.serving import quant

    qdtype = quant.storage_dtype(kv_quant)

    def _attend(q, k, v, qpos, kpos, chunk):
        """Chunked flash (memory O(Lq·chunk)) on shard-local operands."""
        from repro.kernels.ops import _chunked_attention

        return _chunked_attention(
            q, k, v, q_pos=qpos, kv_pos=kpos, q_seg=None, kv_seg=None,
            causal=causal, local_only=False, contributed=None, window=window,
            soft_cap=soft_cap, sm_scale=sm_scale, chunk=min(chunk, k.shape[1]),
        )

    def _xchg(x):
        """All-gather KV rows over the seq axis — the sync-layer wire.

        With ``kv_quant`` set, rows cross the collective as int8/fp8 codes
        plus per-row-per-head f32 scales (serving/quant.quantize_rows) and
        dequantize on arrival, shrinking exchange bytes by ~dh*4/(dh+4);
        visibility is still decided purely by gathered positions, never by
        the quantized values."""
        if qdtype is None:
            return jax.lax.all_gather(x, ax, axis=1, tiled=True)
        codes, scales = quant.quantize_rows(x, qdtype)
        cg = jax.lax.all_gather(codes, ax, axis=1, tiled=True)
        sg = jax.lax.all_gather(scales, ax, axis=1, tiled=True)
        return quant.dequantize(cg, sg).astype(x.dtype)

    def local_fn(q, k, v, pos):
        return _attend(q, k, v, pos, pos, 512)

    def sync_full_fn(q, k, v, pos):
        kg = _xchg(k)
        vg = _xchg(v)
        pg = jax.lax.all_gather(pos, ax, axis=0, tiled=True)
        return _attend(q, kg, vg, pos, pg, 512)

    def sync_sparse_fn(q, k, v, pos, mass=None, key=None):
        Ls = k.shape[1]
        n_keep = max(1, int(round(exchange_ratio * Ls)))
        idx = _select_rows(
            pos, Ls, n_keep, kv_selection, keys=k, attn_mass=mass,
            rng=key, round_index=round_index,
        )
        ks = jnp.take(k, idx, axis=1)
        vs = jnp.take(v, idx, axis=1)
        ps = jnp.take(pos, idx, axis=0)
        # Invalidate own-shard gathered rows (full local view already present)
        me = jax.lax.axis_index(ax)
        kg = _xchg(ks)
        vg = _xchg(vs)
        pg = jax.lax.all_gather(ps, ax, axis=0, tiled=True)
        # static shard count from the gathered shape (jax.lax.axis_size is
        # not available on JAX 0.4.x, and arange needs a static extent)
        n_shards = pg.shape[0] // n_keep
        owner = jnp.repeat(jnp.arange(n_shards), n_keep)
        pg = jnp.where(owner == me, K.PAD_POS, pg)
        k_all = jnp.concatenate([k, kg], axis=1)
        v_all = jnp.concatenate([v, vg], axis=1)
        p_all = jnp.concatenate([pos, pg], axis=0)
        return _attend(q, k_all, v_all, pos, p_all, 512)

    args = [q, k, v, q_pos]
    specs = [bspec, bspec, bspec, P(ax)]
    if not sync:
        fn = local_fn
    elif exchange_ratio >= 1.0:
        fn = sync_full_fn
    else:
        fn = sync_sparse_fn
        if attn_mass is not None or rng is not None:
            mass = attn_mass if attn_mass is not None else jnp.zeros(
                (q_pos.shape[0],), jnp.float32
            )
            key = rng if rng is not None else jax.random.PRNGKey(0)
            args += [mass, key]
            specs += [P(ax), P(None)]
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=bspec,
        check_vma=False,
    )(*args)


def _select_rows(
    pos, Ls, n_keep, selection, keys=None, attn_mass=None, rng=None,
    round_index=0,
):
    """Static-count per-shard KV row selection for sparse exchange.

    ``keys`` are the shard-local K rows ((B, Ls, nkv, dh)) — consumed by
    ``'keynorm'`` (top-k rows by batch-and-head-summed ||K||_2, the
    adaptive-importance heuristic of core/aggregation.contribution_mask,
    Observation 4). ``'attnmass'`` keeps the top-k rows by ``attn_mass``
    — the accumulated attention mass each cached row received from the
    last decode step's softmax stats — ranking rows by how much queries
    actually USED them rather than by the static key-magnitude proxy
    (keynorm keeps large-norm rows nobody attends to; attnmass drops
    them). ``'random'`` with an ``rng`` key is real seeded sampling:
    ``fold_in(rng, round_index)`` scores every row with iid uniforms and
    keeps the top-k — deterministic per (key, round), uniform over rows,
    still a static-count gather. Without a key it keeps the historical
    deprecation behavior: warn and alias ``'strided'`` (the deterministic
    stand-in with the same per-shard row count).
    """
    if selection == "recency":
        return jnp.arange(Ls - n_keep, Ls)
    if selection == "sink_recency":
        n_sink = max(1, n_keep // 4)
        return jnp.concatenate(
            [jnp.arange(n_sink), jnp.arange(Ls - (n_keep - n_sink), Ls)]
        )
    if selection == "keynorm":
        if keys is None:
            raise ValueError("kv_selection='keynorm' requires the K rows")
        norms = jnp.sqrt(
            jnp.sum(
                jnp.square(keys.astype(jnp.float32)),
                axis=tuple(i for i in range(keys.ndim) if i != 1),
            )
        )  # (Ls,)
        _, idx = jax.lax.top_k(norms, n_keep)
        return jnp.sort(idx)  # keep positional order for the gather
    if selection == "attnmass":
        if attn_mass is None:
            raise ValueError(
                "kv_selection='attnmass' requires the accumulated "
                "attention-mass stats of the last decode step"
            )
        mass = jnp.reshape(attn_mass.astype(jnp.float32), (-1,))[:Ls]
        _, idx = jax.lax.top_k(mass, n_keep)
        return jnp.sort(idx)
    if selection == "random" and rng is not None:
        key = jax.random.fold_in(rng, round_index)
        scores = jax.random.uniform(key, (Ls,))
        _, idx = jax.lax.top_k(scores, n_keep)
        return jnp.sort(idx)
    if selection in ("strided", "random"):
        if selection == "random":
            warnings.warn(
                "kv_selection='random' without an rng key keeps the "
                "deprecated aliasing behavior (deterministic 'strided' "
                "stand-in, same per-shard row count); pass rng= for real "
                "seeded sampling",
                stacklevel=2,
            )
        stride = max(1, Ls // n_keep)
        idx = jnp.arange(n_keep) * stride
        return jnp.minimum(idx, Ls - 1)
    raise ValueError(f"unknown kv_selection {selection!r}")


def decode_exchange_mask(
    attn_mass: jnp.ndarray,  # (B, C) accumulated per-column softmax mass
    exchange_ratio: float,
) -> jnp.ndarray:
    """Per-slot sparse-exchange visibility mask from accumulated decode
    attention mass: keep the top ``ratio * C`` pool columns each slot's
    queries actually USED (``_select_rows`` 'attnmass' ranking — the
    resident decode path's feed for that policy), as a (B, C) bool
    ``contributed`` mask in the standard visibility vocabulary. Static
    count per slot, so the mask is pure DATA under jit (the zero-recompile
    churn pin holds). Columns that never received mass (holes, sentinel
    pages, padding) rank last and drop first."""
    B, C = attn_mass.shape
    n_keep = max(1, int(round(exchange_ratio * C)))

    def one(mass):
        idx = _select_rows(None, C, n_keep, "attnmass", attn_mass=mass)
        return jnp.zeros((C,), bool).at[idx].set(True)

    return jax.vmap(one)(attn_mass)


def gather_memory_once(memory: jnp.ndarray) -> jnp.ndarray:
    """All-gather the encoder memory over the seq axis ONCE before the
    decoder stack (§Perf iteration 6): cross-attention KV is then computed
    from the replicated memory locally at every decoder layer, instead of
    per-layer (B, S_enc, nkv, dh) gathers (12× the traffic for seamless)."""
    ctx = runtime.current()
    assert ctx is not None
    mesh, ax = ctx.mesh, ctx.seq_axis

    return shard_map(
        lambda m: jax.lax.all_gather(m, ax, axis=1, tiled=True),
        mesh=mesh,
        in_specs=P(ctx.bfirst, ax, None),
        out_specs=P(ctx.bfirst, None, None),
        check_vma=False,
    )(memory)


def cross_attention_spmd(
    q: jnp.ndarray,  # (B, S_dec, nq, dh) — S_dec sharded over seq axis
    mk: jnp.ndarray,  # (B, S_enc, nkv, dh) — replicated (memory gathered once)
    mv: jnp.ndarray,
    *,
    memory_replicated: bool = True,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Bidirectional cross-attention: decoder-Q shards attend to the encoder
    memory KV. With ``memory_replicated`` (the default after §Perf it.6) the
    KV needs no per-layer collective."""
    ctx = runtime.current()
    assert ctx is not None
    mesh, ax = ctx.mesh, ctx.seq_axis
    spec = P(ctx.bfirst, ax, None, None)
    mspec = P(ctx.bfirst, None if memory_replicated else ax, None, None)

    def fn(q, mk, mv):
        from repro.kernels.ops import _chunked_attention

        if memory_replicated:
            kg, vg = mk, mv
        else:
            kg = jax.lax.all_gather(mk, ax, axis=1, tiled=True)
            vg = jax.lax.all_gather(mv, ax, axis=1, tiled=True)
        Lq, Lk = q.shape[1], kg.shape[1]
        return _chunked_attention(
            q, kg, vg,
            q_pos=jnp.zeros((Lq,), jnp.int32),
            kv_pos=jnp.zeros((Lk,), jnp.int32),
            q_seg=None, kv_seg=None, causal=False, local_only=False,
            contributed=None, window=None, soft_cap=soft_cap,
            sm_scale=sm_scale, chunk=min(512, Lk),
        )

    return shard_map(
        fn, mesh=mesh, in_specs=(spec, mspec, mspec), out_specs=spec,
        check_vma=False,
    )(q, mk, mv)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _shard_offset(axes, width: int):
    """Global start of this shard's cache slice: linearized index over the
    (possibly multiple) cache axes times the per-shard width."""
    idx = jnp.int32(0)
    mesh = runtime.current().mesh
    for ax in (axes if isinstance(axes, tuple) else (axes,)):
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx * width


def _kv_spec(vec, bfirst, axes):
    """PartitionSpec of a KV-side vector: the cache dim (last) rides the
    cache axes; a per-row (B, C) vector additionally follows the batch."""
    return P(axes) if vec.ndim == 1 else P(bfirst, axes)


def _q_spec(vec, bfirst):
    return P(None) if vec.ndim == 1 else P(bfirst, None)


def decode_attention(
    q: jnp.ndarray,  # (B, S, nq, dh) — replicated over cache axes
    k_cache: jnp.ndarray,  # (B, C, nkv, dh) — C sharded over cache axes
    v_cache: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (S,) or (B, S) global positions of the new tokens
    kv_pos: jnp.ndarray,  # (C,) or (B, C) cache positions, sharded like cache
    sync: bool,
    q_seg: Optional[jnp.ndarray] = None,  # (S,) or (B, S) participant ids
    kv_seg: Optional[jnp.ndarray] = None,  # (C,) or (B, C), sharded like cache
    publisher_lo: int = 0,  # fallback local rule when no segments are given
    causal: bool = True,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Flash-decoding with FedAttn masking over a sequence-sharded cache.

    Each shard builds the shared core's visibility over its cache slice and
    computes partial softmax statistics; pmax/psum over the cache axes
    combine them into the exact full-cache softmax. At local (non-sync)
    layers the mask restricts to the publisher's visible rows — via
    ``local_only`` segment masking when (q_seg, kv_seg) are given (the
    per-row continuous-batching pool passes 2-D vectors: inactive slots
    carry segment -1 and vanish), else via the ``publisher_lo`` position
    rule (rows at positions >= publisher_lo: the publisher's segment and
    all generated tokens)."""
    ctx = runtime.current()
    assert ctx is not None
    mesh = ctx.mesh
    axes = ctx.cache_axes
    cache_spec = P(ctx.bfirst, axes, None, None)
    q_spec = P(ctx.bfirst, None, None, None)

    use_seg = q_seg is not None and kv_seg is not None
    args = [q, k_cache, v_cache, kv_pos, q_pos]
    specs = [
        q_spec, cache_spec, cache_spec,
        _kv_spec(kv_pos, ctx.bfirst, axes), _q_spec(q_pos, ctx.bfirst),
    ]
    if use_seg:
        args += [q_seg, kv_seg]
        specs += [_q_spec(q_seg, ctx.bfirst), _kv_spec(kv_seg, ctx.bfirst, axes)]

    def fn(q, kc, vc, kpos, qpos, qseg=None, kseg=None):
        mask = K.visibility(
            qpos, kpos, qseg, kseg,
            causal=causal,
            local_only=(not sync) and use_seg,
            window=window,
            publisher_lo=None if (sync or use_seg) else publisher_lo,
        )
        m, l, acc = K.masked_attention(
            q, kc, vc, mask, soft_cap=soft_cap, sm_scale=sm_scale,
            return_stats=True,
        )
        # combine partial stats across cache shards
        m_g = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axes)
        acc_g = jax.lax.psum(acc * corr.transpose(0, 2, 1)[..., None], axes)
        out = acc_g / jnp.maximum(l_g, 1e-20).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=q_spec,
        check_vma=False,
    )(*args)


def paged_decode_attention(
    q: jnp.ndarray,  # (B, S, nq, dh) — replicated over cache axes
    pk: jnp.ndarray,  # (num_pages, page_size, nkv, dh) — PAGES sharded
    pv: jnp.ndarray,
    pages: jnp.ndarray,  # (B, P') int32 page tables — replicated
    *,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,  # (P'*ps,) or (B, P'*ps) linear positions, replicated
    sync: bool,
    q_seg: Optional[jnp.ndarray] = None,
    kv_seg: Optional[jnp.ndarray] = None,
    publisher_lo: int = 0,
    causal: bool = True,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    kv_scales: Optional[tuple] = None,  # (sk, sv) (num_pages, nkv) f32
    contributed: Optional[jnp.ndarray] = None,
    backend: Optional[str] = None,
    return_mass: bool = False,
) -> jnp.ndarray:
    """Flash-decoding over a page-sharded physical pool.

    The pool shards over *pages* (each shard owns a contiguous run of
    physical pages); page tables and position/segment vectors replicate.
    Each shard gathers only the table entries landing in its page run —
    every other column (other shards' pages AND sentinel entries, which
    are >= every shard's upper bound) gets ``kv_pos → PAD_POS`` so the
    shared visibility removes it — and the per-shard partial softmax
    stats combine with the exact same pmax/psum as
    :func:`decode_attention`. No collective touches the pool itself.

    ``backend='pallas'`` swaps the in-shard gather+masked_attention for
    the fused paged flash-decode kernel (kernels/flash_decode.py) in its
    ``return_stats`` form: not-mine and sentinel table entries rebase to
    the shard-local sentinel page id, the kernel's own pre-pass masks
    those columns, and the partial stats feed the SAME pmax/psum combine
    — shard-local kernel + existing collective, per the core
    "Flash-decode rules" contract.

    ``kv_scales`` marks a quantized pool (int8/fp8 codes): the scales
    shard over pages exactly like the pool and the in-shard gather (or
    the kernel's dequant-at-load) dequantizes (serving/quant contract)
    before the softmax — clamped not-mine columns dequant garbage just
    like they gather garbage, and the PAD_POS mask hides both.

    ``return_mass`` additionally returns the per-column normalized
    attention mass (B, P'*ps), psum-reduced over shards — the
    ``'attnmass'`` accumulator feed; ``contributed`` thins sync-layer
    cross-segment visibility (the decode-time sparse exchange)."""
    ctx = runtime.current()
    assert ctx is not None
    axes = ctx.cache_axes
    pool_spec = P(axes, None, None, None)
    scale_spec = P(axes, None)
    q_spec = P(ctx.bfirst, None, None, None)

    use_seg = q_seg is not None and kv_seg is not None
    use_ct = use_seg and sync and contributed is not None
    args = [q, pk, pv, pages, kv_pos, q_pos]
    specs = [
        q_spec, pool_spec, pool_spec, P(ctx.bfirst, None),
        _q_spec(kv_pos, ctx.bfirst), _q_spec(q_pos, ctx.bfirst),
    ]
    if use_seg:
        args += [q_seg, kv_seg]
        specs += [_q_spec(q_seg, ctx.bfirst), _q_spec(kv_seg, ctx.bfirst)]
    if kv_scales is not None:
        args += [kv_scales[0], kv_scales[1]]
        specs += [scale_spec, scale_spec]
    if use_ct:
        args += [contributed]
        specs += [_q_spec(contributed, ctx.bfirst)]

    def fn(q, pk, pv, pg, kpos, qpos, *rest):
        rest = list(rest)
        qseg = rest.pop(0) if use_seg else None
        kseg = rest.pop(0) if use_seg else None
        sk, sv = (rest.pop(0), rest.pop(0)) if kv_scales is not None else (None, None)
        ct = rest.pop(0) if use_ct else None
        n_local, ps = pk.shape[0], pk.shape[1]
        lo = _shard_offset(axes, n_local)
        B, Pp = pg.shape
        Lk = Pp * ps
        mine = (pg >= lo) & (pg < lo + n_local)  # (B, P')
        lonly = (not sync) and use_seg
        plo = None if (sync or use_seg) else publisher_lo
        p = None
        if backend == "pallas":
            from repro.kernels import flash_decode as _fd

            # rebase to shard-local table: not-mine entries become the
            # local sentinel id n_local — the kernel's pre-pass turns
            # their columns into PAD_POS/KERNEL_PAD_SEGMENT exactly like
            # the colm masking below
            local_pg = jnp.where(mine, pg - lo, n_local).astype(jnp.int32)
            res = _fd.paged_flash_decode(
                q, pk, pv, local_pg, q_pos=qpos, kv_pos=kpos, q_seg=qseg,
                kv_seg=kseg, causal=causal, local_only=lonly,
                contributed=ct, window=window, soft_cap=soft_cap,
                sm_scale=sm_scale, publisher_lo=plo, k_scales=sk,
                v_scales=sv, return_stats=True, return_mass=return_mass,
            )
            (m, l, acc), p = res[:3], res[3] if return_mass else None
        else:
            local = jnp.where(mine, pg - lo, 0)
            k = jnp.take(pk, local, axis=0).reshape(B, Lk, *pk.shape[2:])
            v = jnp.take(pv, local, axis=0).reshape(B, Lk, *pv.shape[2:])
            if sk is not None:
                from repro.serving import quant

                ssk = jnp.repeat(jnp.take(sk, local, axis=0), ps, axis=1)
                ssv = jnp.repeat(jnp.take(sv, local, axis=0), ps, axis=1)
                k = quant.dequantize(k, ssk)
                v = quant.dequantize(v, ssv)
            colm = jnp.repeat(mine, ps, axis=1)  # (B, Lk)
            kpos = jnp.where(colm, jnp.broadcast_to(jnp.atleast_2d(kpos), (B, Lk)), K.PAD_POS)
            if kseg is not None:
                kseg = jnp.where(
                    colm, jnp.broadcast_to(jnp.atleast_2d(kseg), (B, Lk)),
                    K.KERNEL_PAD_SEGMENT,
                )
            mask = K.visibility(
                qpos, kpos, qseg, kseg,
                causal=causal,
                local_only=lonly,
                contributed=ct,
                window=window,
                publisher_lo=plo,
            )
            res = K.masked_attention(
                q, k, v, mask, soft_cap=soft_cap, sm_scale=sm_scale,
                return_stats=True, return_probs=return_mass,
            )
            (m, l, acc), p = res[:3], res[3] if return_mass else None
        m_g = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axes)
        acc_g = jax.lax.psum(acc * corr.transpose(0, 2, 1)[..., None], axes)
        out = acc_g / jnp.maximum(l_g, 1e-20).transpose(0, 2, 1)[..., None]
        out = out.astype(q.dtype)
        if not return_mass:
            return out
        # p is relative to the shard-local m — the same exp(m - m_g)
        # correction that merges l/acc rebases it to the global softmax
        w = p * corr[..., None] / jnp.maximum(l_g, 1e-20)[..., None]
        mass = jax.lax.psum(jnp.sum(w, axis=(1, 2)), axes)  # (B, Lk)
        return out, mass

    return shard_map(
        fn,
        mesh=ctx.mesh,
        in_specs=tuple(specs),
        out_specs=(q_spec, P(ctx.bfirst, None)) if return_mass else q_spec,
        check_vma=False,
    )(*args)


def paged_kv_write(
    pk: jnp.ndarray,  # (num_pages, page_size, nkv, dh) — PAGES sharded
    pv: jnp.ndarray,
    k_new: jnp.ndarray,  # (B, S_new, nkv, dh) — replicated
    v_new: jnp.ndarray,
    pages: jnp.ndarray,  # (B, P') page tables — replicated
    cache_len: jnp.ndarray,  # (B,) per-row write frontiers (linear positions)
    kv_scales: Optional[tuple] = None,  # (sk, sv) (num_pages, nkv) f32
):
    """Per-row KV write through page tables into a page-sharded pool: each
    shard resolves every row's frontier to a (page, offset) and scatters
    only the entries whose page lands in its run — everything else (other
    shards' pages, sentinel table entries, frontiers coasting past the
    table) drops via scatter OOB semantics. No collective.

    With ``kv_scales`` the pool holds int8/fp8 codes: the shard-local
    scatter routes through ``serving.quant.paged_write`` (scatter-max
    scales + ratio rescale), with the same local-sentinel drop semantics
    — not-mine entries map to page ``n_local`` which both the scale
    scatter and the code scatter drop. Returns a 4-tuple
    ``(pk, pv, sk, sv)`` in that case, else the usual ``(pk, pv)``."""
    ctx = runtime.current()
    assert ctx is not None
    axes = ctx.cache_axes
    pool_spec = P(axes, None, None, None)
    scale_spec = P(axes, None)
    new_spec = P(ctx.bfirst, None, None, None)

    def _resolve(pg, cl, n_local, ps, B, S_new):
        from repro.serving import paging

        lo = _shard_offset(axes, n_local)
        Cp = pg.shape[1] * ps
        pos = jnp.broadcast_to(
            cl[:, None] + jnp.arange(S_new)[None, :], (B, S_new)
        )
        pslot, off = paging.page_split(jnp.minimum(pos, Cp - 1), ps)
        page_idx = jnp.take_along_axis(pg, pslot, axis=1)
        ok = (pos < Cp) & (page_idx >= lo) & (page_idx < lo + n_local)
        local = jnp.where(ok, page_idx - lo, n_local)  # OOB → drop
        return local, off

    def fn(pk, pv, kn, vn, pg, cl):
        n_local, ps = pk.shape[0], pk.shape[1]
        B, S_new = kn.shape[:2]
        local, off = _resolve(pg, cl, n_local, ps, B, S_new)
        pk = pk.at[local, off].set(kn.astype(pk.dtype), mode="drop")
        pv = pv.at[local, off].set(vn.astype(pv.dtype), mode="drop")
        return pk, pv

    def fn_quant(pk, pv, sk, sv, kn, vn, pg, cl):
        from repro.serving import quant

        n_local, ps = pk.shape[0], pk.shape[1]
        B, S_new = kn.shape[:2]
        local, off = _resolve(pg, cl, n_local, ps, B, S_new)
        pk, sk = quant.paged_write(pk, sk, kn, local, off)
        pv, sv = quant.paged_write(pv, sv, vn, local, off)
        return pk, pv, sk, sv

    if kv_scales is not None and kv_scales[0] is not None:
        return shard_map(
            fn_quant,
            mesh=ctx.mesh,
            in_specs=(pool_spec, pool_spec, scale_spec, scale_spec,
                      new_spec, new_spec, P(ctx.bfirst, None), P(ctx.bfirst)),
            out_specs=(pool_spec, pool_spec, scale_spec, scale_spec),
            check_vma=False,
        )(pk, pv, kv_scales[0], kv_scales[1], k_new, v_new, pages, cache_len)

    return shard_map(
        fn,
        mesh=ctx.mesh,
        in_specs=(pool_spec, pool_spec, new_spec, new_spec,
                  P(ctx.bfirst, None), P(ctx.bfirst)),
        out_specs=(pool_spec, pool_spec),
        check_vma=False,
    )(pk, pv, k_new, v_new, pages, cache_len)


def decode_kv_write(
    k_cache: jnp.ndarray,  # (B, C, nkv, dh) — C sharded over cache axes
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # (B, S_new, nkv, dh) — replicated
    v_new: jnp.ndarray,
    cache_len: jnp.ndarray,  # (B,) per-row write frontiers
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row KV write into a sequence-sharded cache: each shard scatters
    only the rows whose frontier lands inside its slice (flash-decoding
    write locality — no gather of the cache, no collective at all); rows
    out of the shard's range (and rows coasting past capacity) drop via
    scatter OOB semantics. Mirrors the single-device per-row scatter in
    models/attention.attention_decode_block."""
    ctx = runtime.current()
    assert ctx is not None
    axes = ctx.cache_axes
    cache_spec = P(ctx.bfirst, axes, None, None)
    new_spec = P(ctx.bfirst, None, None, None)

    def fn(kc, vc, kn, vn, cl):
        width = kc.shape[1]
        lo = _shard_offset(axes, width)
        B, S_new = kn.shape[:2]
        cols = cl[:, None] + jnp.arange(S_new)[None, :] - lo  # (B, S_new)
        cols = jnp.where((cols >= 0) & (cols < width), cols, width)  # OOB→drop
        rows = jnp.arange(B)[:, None]
        kc = kc.at[rows, cols].set(kn.astype(kc.dtype))
        vc = vc.at[rows, cols].set(vn.astype(vc.dtype))
        return kc, vc

    return shard_map(
        fn,
        mesh=runtime.current().mesh,
        in_specs=(cache_spec, cache_spec, new_spec, new_spec, P(ctx.bfirst)),
        out_specs=(cache_spec, cache_spec),
        check_vma=False,
    )(k_cache, v_cache, k_new, v_new, cache_len)
