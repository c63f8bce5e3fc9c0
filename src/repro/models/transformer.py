"""Unified decoder-only TransformerLM — config-driven over all assigned
architecture families (dense GQA / MoE / RWKV6 / Mamba-hybrid / VLM & audio
backbones), FedAttn-integrated.

Two application modes:

  * ``loop``  — python loop over layers; supports arbitrary sync schedules,
    trace capture for error analysis, per-layer introspection. Used by
    tests, experiments, small models.
  * ``scan``  — ``lax.scan`` over the repeating layer *pattern* (period);
    HLO size O(period), so 62-layer full-size configs lower fast. Requires
    a periodic sync schedule (the pattern's ``sync`` flags). Used by the
    multi-pod dry-run and full-size lowering.

Parameters are plain pytrees (dict of dicts / lists); ``stack_params``
converts loop-form params to scan-form.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.fedattn import FedAttnContext
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import scopes
from repro.models import ssm as S
from repro.types import LayerSpec, ModelConfig

Params = dict


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def init_layer(rng: jax.Array, spec: LayerSpec, config: ModelConfig) -> Params:
    r1, r2, r3 = jax.random.split(rng, 3)
    p: Params = {"norm1": L.init_norm(config), "norm2": L.init_norm(config)}
    if spec.kind == "attn":
        p["attn"] = A.init_attention(r1, config)
    elif spec.kind == "mamba":
        p["mamba"] = S.init_mamba(r1, config)
    else:  # rwkv
        p["tmix"] = S.init_rwkv(r1, config)
    if spec.kind == "rwkv":
        p["cmix"] = S.init_rwkv_cmix(r2, config)
    elif spec.moe:
        p["moe"] = M.init_moe(r2, config)
    else:
        p["ffn"] = L.init_ffn(r3, config)
    return p


def apply_layer(
    p: Params,
    x: jnp.ndarray,
    ctx: FedAttnContext,
    layer_idx: int,
    spec: LayerSpec,
    config: ModelConfig,
    *,
    sync: Optional[bool] = None,
    backend: Optional[str] = None,
    moe_impl: str = "dense",
    collect_aux: bool = False,
):
    """One pre-LN block (eq. 19 update rule). Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["norm1"], x, config)
    if sync is None:
        sync = ctx.schedule.is_sync(layer_idx)
    if spec.kind == "attn":
        o = A.attention_block(
            p["attn"], h, ctx, layer_idx, spec, config, sync=sync, backend=backend
        )
    elif spec.kind == "mamba":
        o, _, _ = S.mamba_block(p["mamba"], h, ctx, config, sync=sync, backend=backend)
    else:
        o, _, _ = S.rwkv_time_mix(p["tmix"], h, ctx, config, sync=sync, backend=backend)
    x = x + o
    h2 = L.apply_norm(p["norm2"], x, config)
    if spec.kind == "rwkv":
        f, _ = S.rwkv_channel_mix(p["cmix"], h2, ctx, config, sync=sync)
    elif spec.moe:
        from repro.distributed import runtime as _rt

        if moe_impl == "ragged" and _rt.active():
            from repro.distributed import spmd_moe

            if spmd_moe.applicable(config, h2.shape[1]):
                f = spmd_moe.moe_expert_parallel(p["moe"], h2, config)
            else:
                f = M.apply_moe_ragged(p["moe"], h2, config)
        elif moe_impl == "ragged":
            f = M.apply_moe_ragged(p["moe"], h2, config)
        elif collect_aux:
            f, aux = M.apply_moe(p["moe"], h2, config, return_aux=True)
        else:
            f = M.apply_moe(p["moe"], h2, config)
    else:
        f = L.apply_ffn(p["ffn"], h2, config)
    return x + f, aux


# ---------------------------------------------------------------------------
# Decode-step per-layer (cache-carrying)
# ---------------------------------------------------------------------------


def init_layer_cache(
    spec: LayerSpec, config: ModelConfig, batch: int, capacity: int, dtype
) -> Params:
    d = config.d_model
    if spec.kind == "attn":
        nkv, dh = config.n_kv_heads, config.head_dim
        return {
            "k": jnp.zeros((batch, capacity, nkv, dh), dtype),
            "v": jnp.zeros((batch, capacity, nkv, dh), dtype),
        }
    if spec.kind == "mamba":
        d_in = config.mamba_expand * d
        return {
            "state": jnp.zeros((batch, d_in, config.mamba_d_state), jnp.float32),
            "conv": jnp.zeros((batch, config.mamba_d_conv - 1, d_in), dtype),
        }
    dh = config.rwkv_head_dim
    H = d // dh
    return {
        "state": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "shift_t": jnp.zeros((batch, 1, d), dtype),
        "shift_c": jnp.zeros((batch, 1, d), dtype),
    }


def init_paged_layer_cache(
    spec: LayerSpec, config: ModelConfig, batch: int, num_pages: int,
    page_size: int, dtype, kv_quant: Optional[str] = None,
    mass_width: Optional[int] = None,
) -> Params:
    """Paged variant of :func:`init_layer_cache`: attention layers get a
    *shared* physical pool ``pk``/``pv`` of shape (num_pages, page_size,
    nkv, dh) — no batch dim; slots address it through int32 page tables
    (serving/paging.py). Recurrent layers keep per-slot state rows.

    With ``kv_quant`` ('int8'/'fp8', serving/quant.py) the pool leaves
    store codes in the codec dtype plus sibling per-page-per-head scale
    leaves ``sk``/``sv`` of shape (num_pages, nkv) f32 — scales are DATA
    like page tables, never shapes.

    With ``mass_width`` (the slot capacity, set when the engine's
    ``kv_selection='attnmass'`` policy needs decode-time stats) attention
    layers additionally carry an ``am`` (batch, mass_width) f32 leaf: the
    per-slot accumulated attention mass each pool column received from
    the decode steps' softmax stats — DATA riding the cache pytree, reset
    on slot admission (paged_slot_write), consumed by
    spmd_attention.decode_exchange_mask. The paged layout only: a dense
    layout has no per-column pool to rank."""
    if spec.kind == "attn":
        from repro.serving import quant

        nkv, dh = config.n_kv_heads, config.head_dim
        sd = quant.storage_dtype(kv_quant)
        if sd is not None:
            c = {
                "pk": jnp.zeros((num_pages, page_size, nkv, dh), sd),
                "pv": jnp.zeros((num_pages, page_size, nkv, dh), sd),
                "sk": jnp.zeros((num_pages, nkv), jnp.float32),
                "sv": jnp.zeros((num_pages, nkv), jnp.float32),
            }
        else:
            c = {
                "pk": jnp.zeros((num_pages, page_size, nkv, dh), dtype),
                "pv": jnp.zeros((num_pages, page_size, nkv, dh), dtype),
            }
        if mass_width is not None:
            c["am"] = jnp.zeros((batch, mass_width), jnp.float32)
        return c
    return init_layer_cache(spec, config, batch, page_size, dtype)


def init_paged_cache(
    config: ModelConfig, batch: int, num_pages: int, page_size: int,
    *, plan: Optional["ScanPlan"] = None, kv_quant: Optional[str] = None,
    mass_width: Optional[int] = None,
):
    """Block-paged decode caches, loop or scan form (mirrors init_cache /
    init_cache_scan; scan form stacks pool leaves to (n_periods, num_pages,
    page_size, nkv, dh)). The stacked layout is what the scheduler, the
    slot writes and the prefix cache see; inside the decode step's layer
    scan the pool rides the carry as one flat pool of ``n_periods *
    num_pages`` pages, period ``i`` addressing its own pages at offset
    ``i * num_pages`` (apply_layers_decode_scan — except under a serving
    mesh, whose page-sharded pool stays a scanned input). ``kv_quant``
    selects a quantized pool codec
    (attention-only stacks; see init_paged_layer_cache); ``mass_width``
    adds the per-slot attention-mass accumulator leaf (the 'attnmass'
    decode-stats feed, ibid.)."""
    if kv_quant not in (None, "none") and any(
        s.kind != "attn" for s in config.layer_specs()
    ):
        raise NotImplementedError(
            "quantized KV (kv_quant=...) requires an attention-only stack: "
            "recurrent layers (mamba/rwkv) carry per-slot STATE, not "
            "per-position KV, so there is no page/row granularity to attach "
            "scales to — recurrent-state quantization is a different "
            "contract (scale re-derivation on every state update). Run "
            "SSM/hybrid pools with kv_quant=None."
        )
    dt = jnp.dtype(config.dtype)
    mk = lambda s: init_paged_layer_cache(
        s, config, batch, num_pages, page_size, dt, kv_quant, mass_width
    )
    if plan is not None:
        per = [mk(s) for s in plan.specs]
        stacked = jax.tree.map(
            lambda x: jnp.zeros((plan.n_periods,) + x.shape, x.dtype), per
        )
        return {
            "stacked": stacked,
            "remainder": [mk(s) for s in plan.remainder_specs],
        }
    return [mk(s) for s in config.layer_specs()]


def _gather_pool(pool, pages, scales=None):
    """Densify page tables through a physical pool: pool (..., N, ps, nkv,
    dh) + pages (B, P') int32 → (..., B, P'*ps, nkv, dh). Gather CLAMPS, so
    sentinel entries (>= N) read the last physical page — callers must mask
    those columns (kv_pos → PAD_POS) before any visibility decision.

    With ``scales`` ((..., N, nkv) f32 — a quantized pool's sibling scale
    leaf) the gathered codes dequantize to f32 HERE, inside the gather, so
    every downstream consumer sees the dense contract. Sentinel columns
    dequantize clamped garbage; that is fine — the PAD_POS masking rule
    hides them before any score is computed."""
    axis = pool.ndim - 4
    N, ps = pool.shape[axis], pool.shape[axis + 1]
    B, Pp = pages.shape
    idx = jnp.minimum(pages, N - 1)
    out = jnp.take(pool, idx, axis=axis)
    out = out.reshape(out.shape[:axis] + (B, Pp * ps) + out.shape[-2:])
    if scales is None:
        return out
    from repro.serving import quant

    s = jnp.repeat(jnp.take(scales, idx, axis=axis), ps, axis=axis + 1)
    return quant.dequantize(out, s)


def _scatter_pool(pool, dense, dst_pages):
    """Inverse of :func:`_gather_pool`: write a dense per-slot cache
    (..., B, P'*ps, nkv, dh) into the pool at ``dst_pages`` (B, P').
    Scatter DROPS out-of-bounds rows, so sentinel entries are no-ops —
    bucket-padding garbage beyond a slot's allocation never lands."""
    axis = pool.ndim - 4
    ps = pool.shape[axis + 1]
    B, Pp = dst_pages.shape
    blk = dense.reshape(dense.shape[:axis] + (B * Pp, ps) + dense.shape[-2:])
    blk = blk.astype(pool.dtype)
    idx = dst_pages.reshape(-1)
    if axis == 0:
        return pool.at[idx].set(blk, mode="drop")
    return pool.at[:, idx].set(blk, mode="drop")


def _scatter_pool_quant(pool, scales, dense, dst_pages):
    """Quantized :func:`_scatter_pool`: each written page quantize-RESETS
    (serving/quant.quantize_block — fresh per-page scales, so a freed page
    reused by a new slot never inherits the previous resident's amax) and
    codes + scales scatter with the same drop semantics. Sentinel dst
    entries drop BOTH leaves, so shared prefix pages — which admission
    keeps at the sentinel — keep their codes AND scales immutable."""
    from repro.serving import quant

    axis = pool.ndim - 4
    ps = pool.shape[axis + 1]
    B, Pp = dst_pages.shape
    blk = dense.reshape(dense.shape[:axis] + (B * Pp, ps) + dense.shape[-2:])
    codes, s = quant.quantize_block(blk, pool.dtype)
    idx = dst_pages.reshape(-1)
    if axis == 0:
        return (pool.at[idx].set(codes, mode="drop"),
                scales.at[idx].set(s, mode="drop"))
    return (pool.at[:, idx].set(codes, mode="drop"),
            scales.at[:, idx].set(s, mode="drop"))


def gather_paged_cache(cache, pages):
    """Dense transient caches for a batch of slots of a paged pool cache:
    attention leaves gather ``pages`` (B, P') into (B, P'*ps, nkv, dh)
    k/v; recurrent leaves come back as fresh zero state for B rows (the
    suffix-prefill consumer is attn-only — enforced by the scheduler)."""
    B = pages.shape[0]
    scan_form = isinstance(cache, dict)

    def rec(x):
        if scan_form:
            return jnp.zeros((x.shape[0], B) + x.shape[2:], x.dtype)
        return jnp.zeros((B,) + x.shape[1:], x.dtype)

    def layer(c):
        if "pk" in c:
            # quantized pools ("sk" present) dequantize inside the gather —
            # the dense transient is f32 regardless of the pool codec
            return {"k": _gather_pool(c["pk"], pages, c.get("sk")),
                    "v": _gather_pool(c["pv"], pages, c.get("sv"))}
        return {key: rec(val) for key, val in c.items()}

    if scan_form:
        return {
            "stacked": [layer(c) for c in cache["stacked"]],
            "remainder": [layer(c) for c in cache["remainder"]],
        }
    return [layer(c) for c in cache]


def paged_slot_write(cache, batch, dst_pages, slots):
    """Write an admitted group's dense transient caches into the paged
    pool: attention leaves scatter page blocks at ``dst_pages`` ((B, P')
    int32, sentinel entries drop), recurrent leaves write rows at
    ``slots`` ((B,) int32, out-of-bounds padding rows drop)."""
    scan_form = isinstance(cache, dict)

    def layer(pc, bc):
        if "pk" in pc:
            if "sk" in pc:
                pk, sk = _scatter_pool_quant(pc["pk"], pc["sk"], bc["k"],
                                             dst_pages)
                pv, sv = _scatter_pool_quant(pc["pv"], pc["sv"], bc["v"],
                                             dst_pages)
                out = {"pk": pk, "pv": pv, "sk": sk, "sv": sv}
            else:
                out = {"pk": _scatter_pool(pc["pk"], bc["k"], dst_pages),
                       "pv": _scatter_pool(pc["pv"], bc["v"], dst_pages)}
            if "am" in pc:
                # admitted slots restart with zero accumulated mass (the
                # previous resident's stats must not rank the new pages);
                # out-of-bounds padding rows drop like every slot write
                if scan_form:
                    out["am"] = pc["am"].at[:, slots].set(0.0, mode="drop")
                else:
                    out["am"] = pc["am"].at[slots].set(0.0, mode="drop")
            return out
        if scan_form:
            return {k: pc[k].at[:, slots].set(bc[k].astype(pc[k].dtype))
                    for k in pc}
        return {k: pc[k].at[slots].set(bc[k].astype(pc[k].dtype)) for k in pc}

    if scan_form:
        return {
            "stacked": [
                layer(p, b) for p, b in zip(cache["stacked"], batch["stacked"])
            ],
            "remainder": [
                layer(p, b)
                for p, b in zip(cache["remainder"], batch["remainder"])
            ],
        }
    return [layer(p, b) for p, b in zip(cache, batch)]


def apply_layer_decode(
    p: Params,
    cache: Params,
    x: jnp.ndarray,  # (B, S_new, D)
    cache_len,
    ctx: FedAttnContext,  # decode-step context
    layer_idx: int,
    spec: LayerSpec,
    config: ModelConfig,
    *,
    sync: Optional[bool] = None,
    backend: Optional[str] = None,
    moe_impl: str = "dense",
    contributed: Optional[jnp.ndarray] = None,
    pages: Optional[jnp.ndarray] = None,
):
    """One decode block. Returns (x, new_cache). ``contributed`` is this
    layer's sparse-KV-exchange row during bulk prefill-via-decode.

    Per-row vectors: ``ctx`` (the decode context) may carry 2-D ``(B, S)``
    positions/segments and ``(B, capacity)`` kv vectors — the batched
    contract of repro.kernels.core. Attention consumes them as visibility;
    the recurrent blocks (mamba/rwkv) derive per-row validity, reset and
    shift masks from the same segments (models/ssm docstring), so padded
    suffix tokens (segment -1) are identity state updates and the
    conv/token-shift carries come from each row's last REAL token."""
    if sync is None:
        sync = ctx.schedule.is_sync(layer_idx)
    h = L.apply_norm(p["norm1"], x, config)
    new_cache = dict(cache)
    if spec.kind == "attn":
        if "pk" in cache:
            am = cache.get("am")
            if "sk" in cache:
                # quantized pool: the write re-encodes through the scale
                # scatter-max and the read dequantizes inside the gather
                # (or, on the pallas backend, at the kernel's block load)
                res = A.attention_decode_block(
                    p["attn"], h, cache["pk"], cache["pv"], cache_len, ctx,
                    layer_idx, spec, config, sync=sync, backend=backend,
                    contributed=contributed, pages=pages,
                    kv_scales=(cache["sk"], cache["sv"]), attn_mass=am,
                )
                o, kc, vc, sk, sv = res[:5]
                new_cache["sk"], new_cache["sv"] = sk, sv
            else:
                res = A.attention_decode_block(
                    p["attn"], h, cache["pk"], cache["pv"], cache_len, ctx,
                    layer_idx, spec, config, sync=sync, backend=backend,
                    contributed=contributed, pages=pages, attn_mass=am,
                )
                o, kc, vc = res[:3]
            if am is not None:
                new_cache["am"] = res[-1]
            new_cache["pk"], new_cache["pv"] = kc, vc
        else:
            o, kc, vc = A.attention_decode_block(
                p["attn"], h, cache["k"], cache["v"], cache_len, ctx,
                layer_idx, spec, config, sync=sync, backend=backend,
                contributed=contributed,
            )
            new_cache["k"], new_cache["v"] = kc, vc
    elif spec.kind == "mamba":
        # single-token decode: state continues (sync irrelevant); bulk
        # prefill-via-decode (S_new > 1, engine) honors the real sync flag
        ssm_sync = sync if x.shape[1] > 1 else True
        o, st, cv = S.mamba_block(
            p["mamba"], h, ctx, config, sync=ssm_sync,
            state=cache["state"], conv_state=cache["conv"], backend=backend,
        )
        new_cache["state"], new_cache["conv"] = st, cv
    else:
        ssm_sync = sync if x.shape[1] > 1 else True
        o, st, sh = S.rwkv_time_mix(
            p["tmix"], h, ctx, config, sync=ssm_sync,
            state=cache["state"], shifted=cache["shift_t"], backend=backend,
        )
        new_cache["state"], new_cache["shift_t"] = st, sh
    x = x + o
    with jax.named_scope(scopes.MLP):
        h2 = L.apply_norm(p["norm2"], x, config)
        if spec.kind == "rwkv":
            # same single-token/bulk split as time-mix: S=1 decode continues
            # the shift carry (sync semantics), bulk prefill-via-decode honors
            # the layer's real flag so local-layer channel-mix token shifts
            # mask at segment boundaries exactly as the forward path does
            f, sh2 = S.rwkv_channel_mix(
                p["cmix"], h2, ctx, config, sync=ssm_sync,
                shifted=cache["shift_c"],
            )
            new_cache["shift_c"] = sh2
        elif spec.moe:
            from repro.distributed import runtime as _rt

            if moe_impl == "ragged" and _rt.active():
                from repro.distributed import spmd_moe

                if spmd_moe.applicable(config, h2.shape[1]):
                    f = spmd_moe.moe_expert_parallel(p["moe"], h2, config)
                else:
                    f = M.apply_moe_ragged(p["moe"], h2, config)
            elif moe_impl == "ragged":
                f = M.apply_moe_ragged(p["moe"], h2, config)
            else:
                f = M.apply_moe(p["moe"], h2, config)
        else:
            f = L.apply_ffn(p["ffn"], h2, config)
    return x + f, new_cache


# ---------------------------------------------------------------------------
# Scan-over-layers decode (ScanPlan + stacked caches)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ScanPlan:
    """Static description of a scan-over-layers lowering of the decode path.

    The model body is viewed as ``n_periods`` repetitions of a ``period``-
    layer unit whose sync flags (from the SyncSchedule) are identical in
    every repetition, plus trailing ``remainder`` layers applied in a python
    loop. ``period`` is a multiple of the architecture's pattern period but
    may be larger — e.g. a homogeneous (period-1) stack with sync every H-th
    layer scans over an H-layer unit. Traced HLO is O(period), not
    O(n_layers), so deep configs compile in near-constant time.
    """

    period: int
    specs: tuple[LayerSpec, ...]  # one scan unit, len == period
    sync: tuple[bool, ...]  # schedule flags of the unit
    n_periods: int
    remainder_specs: tuple[LayerSpec, ...]
    remainder_sync: tuple[bool, ...]

    @property
    def syncs_per_period(self) -> int:
        return sum(self.sync)

    @staticmethod
    def from_schedule(config: ModelConfig, schedule) -> Optional["ScanPlan"]:
        """Smallest valid plan for ``schedule``, or None when the schedule is
        not periodic over the pattern body (scan lowering inapplicable)."""
        base_p = len(config.pattern)
        n_body = config.n_periods * base_p
        mask = tuple(schedule.mask)
        specs = config.layer_specs()
        for p in range(base_p, n_body // 2 + 1, base_p):
            if n_body % p:
                continue
            base = mask[:p]
            if all(mask[s : s + p] == base for s in range(0, n_body, p)):
                return ScanPlan(
                    period=p,
                    specs=tuple(specs[:p]),
                    sync=base,
                    n_periods=n_body // p,
                    remainder_specs=tuple(config.pattern_remainder),
                    remainder_sync=tuple(mask[n_body:]),
                )
        return None


def init_cache_scan(
    config: ModelConfig, plan: ScanPlan, batch: int, capacity: int
) -> Params:
    """Decode caches in scan form: ``stacked`` mirrors one scan unit (a list
    of per-slot caches) with every leaf gaining a leading (n_periods,) dim;
    ``remainder`` is a plain list for the trailing layers."""
    dt = jnp.dtype(config.dtype)
    per = [init_layer_cache(s, config, batch, capacity, dt) for s in plan.specs]
    stacked = jax.tree.map(
        lambda x: jnp.zeros((plan.n_periods,) + x.shape, x.dtype), per
    )
    remainder = [
        init_layer_cache(s, config, batch, capacity, dt)
        for s in plan.remainder_specs
    ]
    return {"stacked": stacked, "remainder": remainder}


def cache_pspecs(cache, cache_axes):
    """PartitionSpec tree for a decode cache (loop or scan form) with the
    KV length dim sharded over ``cache_axes`` — the sequence-sharded pool
    layout of the SPMD continuous-batching scheduler. Attention leaves
    ``k``/``v`` are ``(..., B, capacity, nkv, dh)`` (a leading
    ``(n_periods,)`` dim in scan form): capacity is always axis ``ndim-3``.
    SSM/RWKV state leaves have no sequence dim and stay replicated."""
    from jax.sharding import PartitionSpec as P

    def leaf(path_key, x):
        if path_key in ("k", "v"):
            return P(*([None] * (x.ndim - 3)), cache_axes, None, None)
        if path_key in ("pk", "pv"):
            # paged pool (..., num_pages, page_size, nkv, dh): shard PAGES,
            # not rows — each shard owns a contiguous run of physical pages
            return P(*([None] * (x.ndim - 4)), cache_axes, None, None, None)
        if path_key in ("sk", "sv"):
            # quantized-pool scales (..., num_pages, nkv): sharded with
            # their pages — a shard holds exactly its pages' scales
            return P(*([None] * (x.ndim - 2)), cache_axes, None)
        return P(*([None] * x.ndim))

    def layer(c):
        return {key: leaf(key, val) for key, val in c.items()}

    if isinstance(cache, dict):  # scan form
        return {
            "stacked": [layer(c) for c in cache["stacked"]],
            "remainder": [layer(c) for c in cache["remainder"]],
        }
    return [layer(c) for c in cache]


#: paged-pool leaves of an attention layer's cache (codes, then the
#: quantized pool's per-page scales) — the leaves the decode scan carries
POOL_KEYS = ("pk", "pv", "sk", "sv")


def apply_layers_decode_scan(
    params: Params,
    cache: Params,
    x: jnp.ndarray,  # (B, S_new, D) embedded input
    cache_len,
    dctx: FedAttnContext,
    config: ModelConfig,
    plan: ScanPlan,
    *,
    backend: Optional[str] = None,
    moe_impl: str = "dense",
    contributed: Optional[jnp.ndarray] = None,  # rounds-first prefill rows
    pages: Optional[jnp.ndarray] = None,  # (B, P') page tables (paged pool)
):
    """All decoder layers as one ``lax.scan`` over the plan's scan units.

    The hidden state is the scan carry; the per-period params [and
    contributed rows] are the scanned inputs, and so are the per-slot
    cache leaves (dense ``k``/``v``, recurrent state, the ``am`` mass
    accumulator), whose updates come back as the stacked outputs — so
    the trace contains each unit's layers exactly once. Per-round
    sparse-exchange rows are sliced per scan step ((n_periods,
    syncs_per_period, ...) reshape), keeping round ordering identical to
    the python-loop path. ``contributed`` is rounds-first: ``(rounds,
    capacity)`` shared rows or ``(rounds, B, capacity)`` per-row rows
    (coalesced multi-request admission — each batch row carries its own
    request's exchange mask).

    A paged pool (``pages`` given) rides the CARRY instead: each stacked
    pool leaf ``(n_periods, N, ...)`` (``pk``/``pv``, and the ``sk``/``sv``
    scales of a quantized pool) is viewed as one flat pool of
    ``n_periods * N`` pages, and period ``i`` addresses its own pages
    through tables offset by ``i * N`` (serving/paging.stacked_pages;
    holes stay holes for the whole flat pool). Each layer writes its new
    rows into the carried buffer in place and reads the pages it needs
    from it, so no period's page array is sliced out and no stacked pool
    output is built. A serving mesh keeps the pool as a scanned input:
    the pool is sharded on its page axis, and merging the period axis
    into it would break the shard-local page ranges.
    Returns (x, new_cache) with the cache still in scan form."""
    from repro.distributed import runtime
    from repro.serving import paging

    spp = plan.syncs_per_period
    contrib_body = None
    if contributed is not None and spp > 0:
        contrib_body = contributed[: plan.n_periods * spp].reshape(
            (plan.n_periods, spp) + contributed.shape[1:]
        )

    n_per = plan.n_periods
    stacked = cache["stacked"]
    n_pages = next((c["pk"].shape[1] for c in stacked if "pk" in c), None)
    carry_pool = (pages is not None and n_pages is not None
                  and not runtime.active())
    carried = POOL_KEYS if carry_pool else ()

    def split(c):  # (carried pool leaves, per-slot leaves) of a layer cache
        return ({k: v for k, v in c.items() if k in carried},
                {k: v for k, v in c.items() if k not in carried})

    pools = [split(c)[0] for c in stacked]
    rest = [split(c)[1] for c in stacked]
    pools = jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]), pools)

    def body(carry, xs):
        h, pools = carry
        period, per_params, per_rest, contrib_rows = xs
        pg = pages
        if carry_pool:
            pg = paging.stacked_pages(pages, period, n_pages, n_per)
        new_pools, new_rest = [], []
        ci = 0
        for i, spec in enumerate(plan.specs):
            row = None
            if contrib_rows is not None and plan.sync[i]:
                row = contrib_rows[ci]
                ci += 1
            h, c = apply_layer_decode(
                per_params[i], {**per_rest[i], **pools[i]}, h, cache_len,
                dctx, 0, spec, config, sync=plan.sync[i], backend=backend,
                moe_impl=moe_impl, contributed=row, pages=pg,
            )
            p, r = split(c)
            new_pools.append(p)
            new_rest.append(r)
        return (h, new_pools), new_rest

    xs = (jnp.arange(n_per, dtype=jnp.int32), params["stacked"], rest,
          contrib_body)
    with jax.named_scope(scopes.LAYER_SCAN):
        (x, pools), rest = jax.lax.scan(body, (x, pools), xs)
    pools = jax.tree.map(lambda v: v.reshape((n_per, -1) + v.shape[1:]), pools)
    new_stacked = [{**r, **p} for r, p in zip(rest, pools)]

    new_rem = []
    ri = plan.n_periods * spp
    for j, spec in enumerate(plan.remainder_specs):
        row = None
        if contributed is not None and plan.remainder_sync[j]:
            row = contributed[ri]
            ri += 1
        x, c = apply_layer_decode(
            params["remainder"][j], cache["remainder"][j], x, cache_len,
            dctx, 0, spec, config, sync=plan.remainder_sync[j],
            backend=backend, moe_impl=moe_impl, contributed=row, pages=pages,
        )
        new_rem.append(c)
    return x, {"stacked": new_stacked, "remainder": new_rem}


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


@dataclass
class TransformerLM:
    config: ModelConfig

    # -- init -----------------------------------------------------------------

    def init(self, rng: jax.Array) -> Params:
        cfg = self.config
        specs = cfg.layer_specs()
        keys = jax.random.split(rng, len(specs) + 3)
        params: Params = {
            "embed": L.init_embedding(keys[-1], cfg),
            "layers": [init_layer(keys[i], s, cfg) for i, s in enumerate(specs)],
            "final_norm": L.init_norm(cfg),
            "head": L.init_lm_head(keys[-2], cfg),
        }
        if cfg.frontend != "none":
            params["frontend_proj"] = L.dense_init(
                keys[-3], (cfg.d_model, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        return params

    # -- embed ----------------------------------------------------------------

    def _embed(self, params, tokens, extra_embeds):
        cfg = self.config
        x = L.embed_tokens(params["embed"], tokens, cfg)
        if extra_embeds is not None:
            P = extra_embeds.shape[1]
            fe = jnp.einsum("bpd,de->bpe", extra_embeds.astype(x.dtype),
                            params["frontend_proj"])
            x = jnp.concatenate([fe, x[:, P:]], axis=1)
        return x

    # -- forward (prefill / train) ---------------------------------------------

    def apply(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, L)
        ctx: FedAttnContext,
        *,
        extra_embeds: Optional[jnp.ndarray] = None,
        backend: Optional[str] = None,
        mode: str = "loop",
        moe_impl: str = "dense",
        capture_trace: bool = False,
        collect_aux: bool = False,
        remat: bool = False,
        head_mode: str = "full",
    ):
        """Returns logits (B, L, V); with capture_trace also the per-layer
        hidden-state list; with collect_aux also the summed router aux loss.

        head_mode: 'full' — logits for every position; 'last' — only the
        final position (prefill); 'none' — return the final-norm hidden
        states instead of logits (callers fuse their own head, e.g. the
        chunked cross-entropy in launch/steps.py)."""
        cfg = self.config
        x = self._embed(params, tokens, extra_embeds)
        trace = []
        aux_total = jnp.zeros((), jnp.float32)
        if mode == "loop":
            for m, (p, spec) in enumerate(zip(params["layers"], cfg.layer_specs())):
                fn = lambda p_, x_, m_=m, s_=spec: apply_layer(
                    p_, x_, ctx, m_, s_, cfg,
                    backend=backend, moe_impl=moe_impl, collect_aux=collect_aux,
                )
                if remat:
                    fn = jax.checkpoint(fn)
                x, aux = fn(p, x)
                aux_total = aux_total + aux
                if capture_trace:
                    trace.append(x)
        elif mode == "scan":
            x = self._apply_scan(
                params, x, ctx, backend=backend, moe_impl=moe_impl, remat=remat
            )
        else:
            raise ValueError(f"unknown mode {mode!r}")
        if head_mode == "last":
            x = x[:, -1:]
        x = L.apply_norm(params["final_norm"], x, cfg)
        if head_mode == "none":
            out: tuple = (x,)
            if capture_trace:
                out += (trace,)
            if collect_aux:
                out += (aux_total,)
            return out if len(out) > 1 else x
        logits = L.apply_lm_head(params["head"], params["embed"], x, cfg)
        out: tuple = (logits,)
        if capture_trace:
            out += (trace,)
        if collect_aux:
            out += (aux_total,)
        return out if len(out) > 1 else logits

    def _apply_scan(self, params, x, ctx, *, backend, moe_impl, remat=False):
        """lax.scan over the repeating pattern (period). Sync flags come from
        the pattern specs (structural), so collectives appear only in sync
        sublayers. Remainder layers run in a trailing python loop."""
        cfg = self.config
        stacked = params.get("stacked")
        if stacked is None:
            raise ValueError("scan mode requires stack_params(params, config)")

        def body(carry, per_params):
            h = carry
            for i, spec in enumerate(cfg.pattern):
                h, _ = apply_layer(
                    per_params[i], h, ctx, 0, spec, cfg,
                    sync=spec.sync, backend=backend, moe_impl=moe_impl,
                )
            return h, None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, stacked)
        offset = cfg.n_periods * len(cfg.pattern)
        for j, spec in enumerate(cfg.pattern_remainder):
            x, _ = apply_layer(
                params["remainder"][j], x, ctx, 0, spec, cfg,
                sync=spec.sync, backend=backend, moe_impl=moe_impl,
            )
        return x

    # -- decode ------------------------------------------------------------------

    def init_cache(
        self, batch: int, capacity: int, *, plan: Optional[ScanPlan] = None
    ):
        """Decode caches: a per-layer list (loop mode), or — given a
        :class:`ScanPlan` — the stacked scan form (see init_cache_scan)."""
        cfg = self.config
        if plan is not None:
            return init_cache_scan(cfg, plan, batch, capacity)
        dt = jnp.dtype(cfg.dtype)
        return [
            init_layer_cache(s, cfg, batch, capacity, dt) for s in cfg.layer_specs()
        ]

    def decode_step(
        self,
        params: Params,
        cache,
        tokens: jnp.ndarray,  # (B, S_new)
        cache_len,
        ctx: FedAttnContext,  # prefill-shaped context; converted internally
        step: int | jnp.ndarray = 0,
        *,
        backend: Optional[str] = None,
        moe_impl: str = "dense",
        dctx: Optional[FedAttnContext] = None,
        mode: str = "loop",
        plan: Optional[ScanPlan] = None,
        pages: Optional[jnp.ndarray] = None,
    ):
        """One autoregressive step. Returns (logits (B, S_new, V), new_cache).

        Paged pool: with ``pages`` ((B, P') int32 page tables — traced
        DATA, never a shape) the cache's attention leaves are the shared
        ``pk``/``pv`` physical pool and both the KV write and the
        attention gather route through the table (serving/paging.py holds
        the geometry convention; sentinel entries >= num_pages are holes).

        Jit-stable: ``cache_len`` and ``step`` may be traced scalars (cache
        capacity is taken from static shapes). Callers running a compiled
        multi-token loop pass ``dctx`` — a decode context advanced from
        ``ctx.decode_template(capacity)`` — to avoid rebuilding the context
        from the prefill-shaped ``ctx`` at every unrolled trace.

        Continuous batching: ``cache_len`` may also be a traced (B,) vector
        — each batch row (KV-pool slot) writes at its own frontier — in
        which case ``dctx`` must carry per-row (B, S_new) positions/segments
        and (B, capacity) kv_segments (see serving/scheduler.py). Works in
        both ``loop`` and ``scan`` modes; the vector just rides through
        apply_layer_decode into the per-row cache scatter. Under an SPMD
        runtime the same step runs against a capacity-sharded cache
        (:func:`cache_pspecs` gives the layout) — attention layers switch
        to the flash-decoding shard_map path, everything else is unchanged.

        Multi-token decode-verify (speculative decoding): ``S_new > 1``
        with a vector ``cache_len`` is one verify tick — row ``b`` carries
        the query block ``[last_tok, d_1..d_k]`` at per-row positions
        ``cache_len[b] .. cache_len[b]+k``. Every layer writes all
        ``S_new`` KV rows BEFORE attending (the decode-layer contract in
        models/attention.py), so position ``i``'s logits see the draft
        rows ``< i`` of the same block while causality hides the rows
        ``> i``; rejected rows need no cleanup — the caller's next verify
        block starts at the accepted frontier and overwrites them before
        any later query can reach them. Holds identically in ``loop`` and
        ``scan`` modes, dense and paged caches, single-device and SPMD
        (serving/scheduler.py ``_verify_fn`` is the canonical caller).

        mode='scan' scans over the layer pattern instead of tracing every
        layer: requires a :class:`ScanPlan` (periodic sync schedule), params
        in scan form (``stack_params``) and the cache from
        ``init_cache(..., plan=plan)``. Traced HLO is O(plan.period)."""
        cfg = self.config
        x = L.embed_tokens(params["embed"], tokens, cfg)
        if dctx is None:
            dctx = ctx.for_decode_step(_cache_capacity(cache), step)
        if mode == "scan":
            if plan is None:
                raise ValueError("decode_step(mode='scan') requires a ScanPlan")
            x, new_cache = apply_layers_decode_scan(
                params, cache, x, cache_len, dctx, cfg, plan,
                backend=backend, moe_impl=moe_impl, pages=pages,
            )
        elif mode == "loop":
            new_cache = []
            for m, (p, spec) in enumerate(zip(params["layers"], cfg.layer_specs())):
                x, c = apply_layer_decode(
                    p, cache[m], x, cache_len, dctx, m, spec, cfg,
                    backend=backend, moe_impl=moe_impl, pages=pages,
                )
                new_cache.append(c)
        else:
            raise ValueError(f"unknown decode mode {mode!r}")
        with jax.named_scope(scopes.HEAD_SAMPLE):
            x = L.apply_norm(params["final_norm"], x, cfg)
            logits = L.apply_lm_head(params["head"], params["embed"], x, cfg)
        return logits, new_cache


def _cache_capacity(cache) -> int:
    if isinstance(cache, dict):  # scan form
        for c in list(cache["stacked"]) + list(cache["remainder"]):
            if "k" in c:
                return c["k"].shape[-3]  # (..., B, capacity, nkv, dh)
        return 1
    for c in cache:
        if "k" in c:
            return c["k"].shape[1]
    # SSM-only model: no KV positions are consumed; smallest valid capacity
    return 1


def stack_params(
    params: Params, config: ModelConfig, period: Optional[int] = None
) -> Params:
    """Convert loop-form params to scan-form: group layers by period and
    stack leaves over the period axis → leading dim n_periods.

    ``period`` defaults to the architecture's pattern period; a ScanPlan may
    ask for a larger multiple (e.g. the sync interval on a homogeneous
    stack) — it must divide the pattern body evenly."""
    if period is None:
        period = len(config.pattern)
    n_body = config.n_periods * len(config.pattern)
    if period <= 0 or n_body % period:
        raise ValueError(f"period {period} does not divide the body ({n_body})")
    n_per = n_body // period
    layers = params["layers"]
    body = layers[: n_per * period]
    remainder = layers[n_per * period:]
    groups = [body[i * period : (i + 1) * period] for i in range(n_per)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    out = dict(params)
    del out["layers"]
    out["stacked"] = stacked
    out["remainder"] = remainder
    return out


def init_stacked(model: TransformerLM, rng: jax.Array) -> Params:
    """Initialize directly in scan form — per-period leaves are created with
    a leading (n_periods,) dim via vmap, so full-size configs never
    materialize a python list of 62 layer pytrees."""
    cfg = model.config
    r_emb, r_head, r_fe, r_stack, r_rem = jax.random.split(rng, 5)

    def init_period(r):
        ks = jax.random.split(r, len(cfg.pattern))
        return [init_layer(ks[i], s, cfg) for i, s in enumerate(cfg.pattern)]

    stacked = jax.vmap(init_period)(jax.random.split(r_stack, cfg.n_periods))
    params: Params = {
        "embed": L.init_embedding(r_emb, cfg),
        "stacked": stacked,
        "remainder": [
            init_layer(jax.random.fold_in(r_rem, j), s, cfg)
            for j, s in enumerate(cfg.pattern_remainder)
        ],
        "final_norm": L.init_norm(cfg),
        "head": L.init_lm_head(r_head, cfg),
    }
    if cfg.frontend != "none":
        params["frontend_proj"] = L.dense_init(
            r_fe, (cfg.d_model, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    return params
