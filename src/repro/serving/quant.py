"""Quantized KV codecs — the ONE home of scale arithmetic (lint FED007).

Two codecs, selected by the *storage dtype* of the paged pool. The dtype IS
the mode: no mode string threads through the gather/write chains — only
``models.transformer.init_paged_cache(..., kv_quant=)`` takes the name, and
every consumer branches on the presence of the sibling scale leaves
(``"sk"``/``"sv"``) in the pool pytree.

=========  ====================  ======================  ===================
mode       storage dtype         scale                   elementwise error
=========  ====================  ======================  ===================
``int8``   ``jnp.int8``          amax / 127 per          <= scale / 2
                                 (page, kv-head)
``fp8``    ``float8_e4m3fn``     amax / 448 per          <= max(|x| * 2^-4,
                                 (page, kv-head)         scale * 2^-10)
=========  ====================  ======================  ===================

Scales are sibling ``(num_pages, nkv)`` f32 arrays in the pool pytree —
traced DATA like page tables (never shapes), so admission/retirement churn
never recompiles. Dequantization happens INSIDE the gather
(``models.transformer._gather_pool``, the ``kernels.ops`` paged fetch, the
SPMD in-shard take), so every attention consumer — ref / chunked / Pallas /
SPMD — sees exactly the dense f32 contract, and visibility is NEVER decided
by quantized values (kernels/core.py "Quantization rules").

Write discipline (the part that keeps parity pinned):

* frontier writes (:func:`paged_write`) scatter-MAX the scales — untouched
  pages keep bit-exact scales and their ratio-1 re-encode is exactly the
  identity; pages whose amax grew rescale their resident codes once, then
  the new rows land encoded under the updated scale;
* admission block writes (:func:`quantize_block`) RESET per page — a freed
  page reused by a new slot must not inherit the previous resident's amax.

fp8 note: ``.astype(float8_e4m3fn)`` SATURATES to nan above +-448 on this
backend, so every encode clips to the code range first.
"""
from __future__ import annotations

import jax.numpy as jnp

#: the opt-in pool/exchange codecs ("none"/None disables quantization)
MODES = ("int8", "fp8")

#: static inspection, not an import-time array (FED003-clean)
_TINY = float(jnp.finfo(jnp.float32).tiny)


def storage_dtype(mode):
    """Pool storage dtype for a ``kv_quant`` mode (None when disabled)."""
    if mode in (None, "none"):
        return None
    if mode == "int8":
        return jnp.dtype(jnp.int8)
    if mode == "fp8":
        return jnp.dtype(jnp.float8_e4m3fn)
    raise ValueError(
        f"unknown kv_quant mode {mode!r}: expected one of {MODES} or 'none'"
    )


def is_quantized(dtype) -> bool:
    """True when ``dtype`` is one of the KV code dtypes."""
    dtype = jnp.dtype(dtype)
    return dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn))


def code_max(dtype) -> float:
    """Largest representable code magnitude (127 for int8, 448 for e4m3)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.int8):
        return 127.0
    if dtype == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0
    raise ValueError(f"{dtype} is not a KV quantization storage dtype")


def _encode(x, scales, dtype):
    """Encode ``x`` (..., dh) f32 under ``scales`` (broadcastable against
    ``x[..., 0]``). Clip-before-cast keeps fp8 from saturating to nan."""
    cmax = code_max(dtype)
    y = x.astype(jnp.float32) / jnp.maximum(scales, _TINY)[..., None]
    y = jnp.clip(y, -cmax, cmax)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        y = jnp.round(y)
    return y.astype(dtype)


def dequantize(codes, scales):
    """codes (..., dh) int8/fp8 + scales (...) f32 aligned with
    ``codes[..., 0]`` → f32. The ONE place codes meet scales on the read
    path; every gather routes through here."""
    return codes.astype(jnp.float32) * scales[..., None]


def quantize_rows(x, dtype):
    """Per-row-per-head codec: x (..., nkv, dh) → (codes, scales (..., nkv)).

    The EXCHANGE codec — each KV row crosses the wire as dh codes plus nkv
    f32 scales (see core.aggregation.exchange_bytes_per_row)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scales = amax / code_max(dtype)
    return _encode(x, scales, dtype), scales


def quantize_block(x, dtype):
    """Per-page-per-head codec: x (..., ps, nkv, dh) → (codes,
    scales (..., nkv)); amax pools over the page's rows AND the head dim.

    Fresh RESET semantics (no max-accumulate) — the admission-scatter
    codec: a reused page never inherits a stale amax."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-3, -1))
    scales = amax / code_max(dtype)
    return _encode(x, scales[..., None, :], dtype), scales


def paged_write(pool, scales, new, page_idx, off):
    """Scatter new KV rows into a quantized pool at the decode frontier.

    ``pool`` (N, ps, nkv, dh) codes + ``scales`` (N, nkv) f32; ``new``
    (B, S, nkv, dh) compute dtype; ``page_idx``/``off`` (B, S) int32 —
    entries >= N DROP (the serving/paging sentinel convention; the SPMD
    shard-local variant drops via a local sentinel the same way).

    Returns ``(pool', scales')``. Scales scatter-max first, so an untouched
    page has ``scales' == scales`` bit-exact and its re-encode ratio is
    EXACTLY 1.0 (the identity — resident codes never drift); a page whose
    amax grew rescales its resident codes once by old/new before the new
    rows land encoded under the grown scale. Only the written pages are
    re-encoded (gathered, rescaled, scattered back), so the cost is
    O(rows written), not O(pool) — the pool may be the flat view of every
    period of a scan-form stack (models/transformer)."""
    cmax = code_max(pool.dtype)
    x = new.astype(jnp.float32)
    row_scales = jnp.max(jnp.abs(x), axis=-1) / cmax  # (B, S, nkv)
    scales2 = scales.at[page_idx].max(row_scales, mode="drop")
    N = pool.shape[0]
    live = jnp.minimum(page_idx, N - 1)
    old_s, new_s = jnp.take(scales, live, axis=0), jnp.take(scales2, live, axis=0)
    ratio = jnp.where(new_s == old_s, 1.0, old_s / jnp.maximum(new_s, _TINY))
    body = jnp.take(pool, live, axis=0).astype(jnp.float32)
    body = body * ratio[..., None, :, None]  # (B, S, ps, nkv, dh)
    if jnp.dtype(pool.dtype) == jnp.dtype(jnp.int8):
        body = jnp.round(body)
    body = jnp.clip(body, -cmax, cmax).astype(pool.dtype)
    # rows of one page carry identical re-encoded pages, so duplicate
    # indices in this scatter agree; sentinel entries drop
    pool = pool.at[page_idx].set(body, mode="drop")
    codes = _encode(x, new_s, pool.dtype)
    return pool.at[page_idx, off].set(codes, mode="drop"), scales2
