"""GQA softmax attention layer, FedAttn-aware.

The layer is where the paper's protocol meets the Transformer: depending on
``sync`` (from the SyncSchedule) the attention runs

  * Phase I  (local):  queries see only same-participant KV (eq. 18), or
  * Phase II (global): queries see the aggregated global KV (eq. 21),
    optionally thinned by the sparse-exchange contribution mask (eq. 37).

Prefill/training operate on the full (B, L, D) sequence with masks; decode
operates against a KV cache. Both call into :mod:`repro.kernels.ops`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fedattn import FedAttnContext
from repro.kernels import ops
from repro.models import layers as L
from repro.models import scopes
from repro.types import LayerSpec, ModelConfig

Params = dict


def init_attention(rng: jax.Array, config: ModelConfig) -> Params:
    d, dh = config.d_model, config.head_dim
    nq, nkv = config.n_heads, config.n_kv_heads
    dt = jnp.dtype(config.dtype)
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p: Params = {
        "wq": L.dense_init(rq, (d, nq * dh), dt),
        "wk": L.dense_init(rk, (d, nkv * dh), dt),
        "wv": L.dense_init(rv, (d, nkv * dh), dt),
        "wo": L.dense_init(ro, (nq * dh, d), dt),
    }
    if config.qkv_bias:
        p["bq"] = jnp.zeros((nq * dh,), dt)
        p["bk"] = jnp.zeros((nkv * dh,), dt)
        p["bv"] = jnp.zeros((nkv * dh,), dt)
    if config.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _project_qkv(
    p: Params, x: jnp.ndarray, config: ModelConfig, positions: jnp.ndarray,
    rope_theta: float,
):
    B, S, d = x.shape
    nq, nkv, dh = config.n_heads, config.n_kv_heads, config.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if config.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, nq, dh)
    k = k.reshape(B, S, nkv, dh)
    v = v.reshape(B, S, nkv, dh)
    if config.qk_norm:
        q = L.rms_head_norm(p["q_norm"], q, config.norm_eps)
        k = L.rms_head_norm(p["k_norm"], k, config.norm_eps)
    q = L.apply_rope(q, positions, rope_theta)
    k = L.apply_rope(k, positions, rope_theta)
    return q, k, v


def _rope_theta_for(spec: LayerSpec, config: ModelConfig) -> float:
    if spec.window is not None and config.rope_theta_local is not None:
        return config.rope_theta_local
    return config.rope_theta


def attention_block(
    p: Params,
    x: jnp.ndarray,  # (B, S, D) — normalized input
    ctx: FedAttnContext,
    layer_idx: int,
    spec: LayerSpec,
    config: ModelConfig,
    *,
    sync: Optional[bool] = None,
    backend: Optional[str] = None,
    return_kv: bool = False,
):
    """Prefill/training attention. ``sync`` overrides the schedule (used by
    scan-over-layers where the flag is structural)."""
    theta = _rope_theta_for(spec, config)
    q, k, v = _project_qkv(p, x, config, ctx.positions, theta)
    if sync is None:
        sync = ctx.schedule.is_sync(layer_idx)

    from repro.distributed import runtime

    if runtime.active() and x.shape[1] % runtime.current().n_seq_shards == 0:
        from repro.distributed import spmd_attention

        out = spmd_attention.prefill_attention(
            q, k, v,
            q_pos=ctx.positions,
            causal=ctx.config.causal,
            sync=sync or not ctx.enabled,
            window=spec.window,
            exchange_ratio=ctx.config.kv_exchange_ratio,
            kv_selection=ctx.config.kv_selection,
            kv_quant=ctx.config.kv_quant,
            soft_cap=config.attn_soft_cap,
        )
        B, S = x.shape[:2]
        y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1), p["wo"])
        if return_kv:
            return y, (k, v)
        return y

    if ctx.per_participant_sync is not None:
        # Fig. 8 adaptive per-participant sync: explicit visibility mask
        mask = ctx.layer_visibility(layer_idx, window=spec.window)
        out = ops.attention_masked(q, k, v, mask, soft_cap=config.attn_soft_cap)
        B, S = x.shape[:2]
        y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1), p["wo"])
        return (y, (k, v)) if return_kv else y

    contributed = None
    if sync and ctx.contributed is not None:
        t = ctx._round_of_layer(layer_idx) % ctx.contributed.shape[0]
        contributed = ctx.contributed[t]
    seg = ctx.segments if ctx.enabled else None
    kv_seg = (ctx.kv_segments if ctx.kv_segments is not None else ctx.segments) if ctx.enabled else None
    out = ops.attention(
        q, k, v,
        q_pos=ctx.positions,
        kv_pos=ctx.kv_positions if ctx.kv_positions is not None else ctx.positions,
        q_seg=seg,
        kv_seg=kv_seg,
        causal=ctx.config.causal,
        local_only=(not sync) and ctx.enabled,
        contributed=contributed,
        window=spec.window,
        soft_cap=config.attn_soft_cap,
        backend=backend,
    )
    B, S = x.shape[:2]
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1), p["wo"])
    if return_kv:
        return y, (k, v)
    return y


def attention_decode_block(
    p: Params,
    x: jnp.ndarray,  # (B, S_new, D) — normalized input (usually S_new = 1)
    k_cache: jnp.ndarray,  # (B, C, nkv, dh)
    v_cache: jnp.ndarray,
    cache_len,  # int or traced scalar: number of valid cache slots
    ctx: FedAttnContext,  # built via for_decode_step
    layer_idx: int,
    spec: LayerSpec,
    config: ModelConfig,
    *,
    sync: Optional[bool] = None,
    backend: Optional[str] = None,
    contributed: Optional[jnp.ndarray] = None,
    pages: Optional[jnp.ndarray] = None,
    kv_scales: Optional[tuple] = None,
    attn_mass: Optional[jnp.ndarray] = None,
):
    """Decode-step attention against the cache; writes the new KV in-place
    (dynamic_update_slice) and returns (y, k_cache, v_cache) — or, with
    ``kv_scales``, (y, k_cache, v_cache, k_scales, v_scales).

    ``attn_mass`` ((B, capacity) f32, paged pool only) is the per-slot
    accumulated attention-mass buffer riding the cache pytree as data (the
    'attnmass' KV-selection feed): the paged attend additionally returns
    this step's per-column softmax mass, the buffer accumulates it, and
    the updated buffer is appended as the LAST element of the return
    tuple. At sync layers with ``kv_exchange_ratio < 1.0`` and
    ``kv_selection='attnmass'``, the accumulated mass also derives this
    step's ``contributed`` sparse-exchange mask
    (``spmd_attention.decode_exchange_mask``) when the caller supplied
    none — the decode-time adaptive KV exchange.

    Quantized pool: ``kv_scales`` is the ``(sk, sv)`` pair of per-page-
    per-head (num_pages, nkv) f32 scale leaves riding next to a quantized
    ``pk``/``pv`` pool (serving/quant.py). The KV write re-encodes through
    the scale scatter-max (untouched pages bit-exact) and the attention
    read dequantizes inside the page gather, so the scoring math below is
    byte-identical to the unquantized path.

    Paged pool: with ``pages`` ((B, P') int32 page tables), ``k_cache`` /
    ``v_cache`` are the *shared* (num_pages, page_size, nkv, dh) physical
    pool. New KV scatters through the table (entries >= num_pages and
    positions past the table's capacity drop — serving/paging.py sentinel
    convention) and the attention gathers each row's pages, masking
    sentinel columns before any visibility decision. Tables are traced
    data, so admission churn never re-specializes this function.

    ``cache_len`` may be a scalar (whole batch at one frontier — classic
    generate) or a (B,) vector (continuous batching: every slot of the KV
    pool sits at its own write frontier; rows are scattered independently,
    and out-of-bounds rows — retired slots coasting past their capacity —
    are dropped by scatter semantics). With a vector ``cache_len``, the
    context's position/segment vectors are per-row too ((B, S_new) /
    (B, capacity)).

    ``contributed`` is the sparse-KV-exchange mask for this layer's
    communication round — (capacity,) shared, or (B, capacity) per-row in
    a coalesced admission batch; only set during bulk prefill-via-decode
    at sync layers (single-token decode attends the full cache).

    Under an active SPMD runtime the cache is sequence-sharded over the
    cache axes: vector-``cache_len`` writes route through the shard-local
    scatter and the attention itself through the flash-decoding partial-
    softmax combine (distributed/spmd_attention.py), with the segment
    vectors carrying the same per-row masking as the single-device path."""
    theta = _rope_theta_for(spec, config)
    with jax.named_scope(scopes.QKV):
        q, k_new, v_new = _project_qkv(p, x, config, ctx.positions, theta)
    S_new = x.shape[1]

    from repro.distributed import runtime

    spmd = runtime.active()
    k_scales = v_scales = None
    if kv_scales is not None:
        k_scales, v_scales = kv_scales
    with jax.named_scope(scopes.KV_WRITE):
        k_cache, v_cache, k_scales, v_scales = _write_kv(
            k_cache, v_cache, k_new, v_new, cache_len, pages, spmd,
            k_scales, v_scales,
        )
    if sync is None:
        sync = ctx.schedule.is_sync(layer_idx)
    B = x.shape[0]
    if pages is None and S_new > 1:
        attn_scope = scopes.PREFILL_ATTENTION
    else:
        attn_scope = scopes.DECODE_ATTENTION

    if pages is not None:
        with jax.named_scope(attn_scope):
            out, attn_mass = _paged_attend(
                q, k_cache, v_cache, pages, ctx, spec, config, sync, spmd,
                backend, contributed, k_scales, v_scales, attn_mass,
            )
        with jax.named_scope(scopes.ATTN_OUT):
            y = jnp.einsum("bse,ed->bsd", out.reshape(B, S_new, -1), p["wo"])
        if kv_scales is not None:
            res = (y, k_cache, v_cache, k_scales, v_scales)
        else:
            res = (y, k_cache, v_cache)
        return res + ((attn_mass,) if attn_mass is not None else ())

    with jax.named_scope(attn_scope):
        if spmd:
            from repro.distributed import spmd_attention

            publisher_lo = (
                ctx.partition.publisher_start(ctx.config.publisher_index)
                if ctx.enabled else 0
            )
            out = spmd_attention.decode_attention(
                q, k_cache, v_cache,
                q_pos=ctx.positions,
                kv_pos=ctx.kv_positions,
                q_seg=ctx.segments if ctx.enabled else None,
                kv_seg=ctx.kv_segments if ctx.enabled else None,
                publisher_lo=publisher_lo,
                sync=sync or not ctx.enabled,
                window=spec.window,
                soft_cap=config.attn_soft_cap,
            )
        else:
            out = ops.decode_attention(
                q, k_cache, v_cache,
                q_pos=ctx.positions,
                kv_pos=ctx.kv_positions,
                q_seg=ctx.segments if ctx.enabled else None,
                kv_seg=ctx.kv_segments if ctx.enabled else None,
                causal=True,
                local_only=(not sync) and ctx.enabled,
                contributed=contributed if (sync and ctx.enabled) else None,
                window=spec.window,
                soft_cap=config.attn_soft_cap,
                backend=backend,
            )
    with jax.named_scope(scopes.ATTN_OUT):
        y = jnp.einsum("bse,ed->bsd", out.reshape(B, S_new, -1), p["wo"])
    return y, k_cache, v_cache


def _write_kv(k_cache, v_cache, k_new, v_new, cache_len, pages, spmd,
              k_scales, v_scales):
    """The decode block's KV write (see :func:`attention_decode_block`):
    through the page tables into the shared pool, per-row frontiers into
    dense rows, or one slice at a scalar frontier. Returns the caches and
    the scales (None unless the pool is quantized)."""
    S_new = k_new.shape[1]
    if pages is not None:
        from repro.serving import paging

        if spmd:
            from repro.distributed import spmd_attention

            if k_scales is not None:
                k_cache, v_cache, k_scales, v_scales = (
                    spmd_attention.paged_kv_write(
                        k_cache, v_cache, k_new, v_new, pages, cache_len,
                        kv_scales=(k_scales, v_scales),
                    )
                )
            else:
                k_cache, v_cache = spmd_attention.paged_kv_write(
                    k_cache, v_cache, k_new, v_new, pages, cache_len
                )
        else:
            N, ps = k_cache.shape[0], k_cache.shape[1]
            Cp = pages.shape[1] * ps
            B = k_new.shape[0]
            pos = jnp.broadcast_to(
                jnp.reshape(cache_len, (-1, 1)) + jnp.arange(S_new)[None, :],
                (B, S_new),
            )
            pslot, off = paging.page_split(jnp.minimum(pos, Cp - 1), ps)
            page_idx = jnp.take_along_axis(pages, pslot, axis=1)
            # positions past the table (retired slots coasting) must not
            # clamp into a real page — force the sentinel so they drop
            page_idx = jnp.where(pos < Cp, page_idx, N)
            if k_scales is not None:
                from repro.serving import quant

                k_cache, k_scales = quant.paged_write(
                    k_cache, k_scales, k_new, page_idx, off
                )
                v_cache, v_scales = quant.paged_write(
                    v_cache, v_scales, v_new, page_idx, off
                )
            else:
                k_cache = k_cache.at[page_idx, off].set(
                    k_new.astype(k_cache.dtype), mode="drop"
                )
                v_cache = v_cache.at[page_idx, off].set(
                    v_new.astype(v_cache.dtype), mode="drop"
                )
    elif jnp.ndim(cache_len) == 1:
        if spmd:
            # sequence-sharded cache (pooled SPMD decode): each shard
            # scatters only the rows landing in its slice — no collective
            from repro.distributed import spmd_attention

            k_cache, v_cache = spmd_attention.decode_kv_write(
                k_cache, v_cache, k_new, v_new, cache_len
            )
        else:
            rows = jnp.arange(k_new.shape[0])[:, None]
            cols = cache_len[:, None] + jnp.arange(S_new)[None, :]
            k_cache = k_cache.at[rows, cols].set(k_new.astype(k_cache.dtype))
            v_cache = v_cache.at[rows, cols].set(v_new.astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), cache_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), cache_len, axis=1)
    return k_cache, v_cache, k_scales, v_scales


def _paged_attend(q, k_cache, v_cache, pages, ctx, spec, config, sync, spmd,
                  backend, contributed, k_scales, v_scales, attn_mass):
    """The paged pool's attention (see :func:`attention_decode_block`).
    Returns the attention output and the updated attention-mass buffer
    (None when the pool carries none)."""
    publisher_lo = (
        ctx.partition.publisher_start(ctx.config.publisher_index)
        if ctx.enabled else 0
    )
    want_mass = attn_mass is not None
    if (
        want_mass and sync and ctx.enabled and contributed is None
        and ctx.config.kv_selection == "attnmass"
        and ctx.config.kv_exchange_ratio < 1.0
    ):
        from repro.distributed import spmd_attention

        contributed = spmd_attention.decode_exchange_mask(
            attn_mass, ctx.config.kv_exchange_ratio
        )
    if spmd:
        from repro.distributed import spmd_attention

        out = spmd_attention.paged_decode_attention(
            q, k_cache, v_cache, pages,
            q_pos=ctx.positions,
            kv_pos=ctx.kv_positions,
            q_seg=ctx.segments if ctx.enabled else None,
            kv_seg=ctx.kv_segments if ctx.enabled else None,
            publisher_lo=publisher_lo,
            sync=sync or not ctx.enabled,
            window=spec.window,
            soft_cap=config.attn_soft_cap,
            kv_scales=None if k_scales is None else (k_scales, v_scales),
            contributed=contributed if (sync and ctx.enabled) else None,
            backend=backend,
            return_mass=want_mass,
        )
    else:
        out = ops.paged_decode_attention(
            q, k_cache, v_cache, pages,
            q_pos=ctx.positions,
            kv_pos=ctx.kv_positions,
            q_seg=ctx.segments if ctx.enabled else None,
            kv_seg=ctx.kv_segments if ctx.enabled else None,
            causal=True,
            local_only=(not sync) and ctx.enabled,
            contributed=contributed if (sync and ctx.enabled) else None,
            window=spec.window,
            soft_cap=config.attn_soft_cap,
            backend=backend,
            k_scales=k_scales,
            v_scales=v_scales,
            return_mass=want_mass,
        )
    if want_mass:
        out, mass = out
        attn_mass = attn_mass + mass
    return out, attn_mass
