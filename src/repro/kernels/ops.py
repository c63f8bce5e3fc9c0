"""Public jit'd kernel wrappers with backend dispatch.

Backends:
  'xla'     pure-JAX implementations — `attention` uses a chunked
            online-softmax (flash-style) scan so compiled memory stays
            O(L · chunk) even at 500k context; RWKV/Mamba use lax.scan.
            This is the default on CPU and inside the SPMD dry-run.
  'pallas'  the Pallas TPU kernels (kernels/flash_attention.py etc.),
            compiled on a TPU and run by the JAX interpreter elsewhere
            (kernels.core.pallas_interpret) — the CPU kernel tests.
  'ref'     the pure-jnp oracles (kernels/ref.py), O(L^2) memory; smallest
            code path, used for tests and tiny models.

All wrappers share the FedAttn masking vocabulary: global positions,
participant segment ids, `local_only` (Phase-I local attention) and
`contributed` (sparse KV exchange at sync layers).

Grouped-query attention (nq query heads over nkv KV heads, g = nq // nkv)
is grouped in the xla/ref bodies, never broadcast: query head
h = kv·g + j is contracted against kv head `kv` at K/V's own nkv heads
(kernels.core.grouped_scores / grouped_values), so no K/V copy at nq
heads is ever built. The Pallas kernels group the same way (head h reads
kv head h // g).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import core as _core
from repro.kernels import ref as _ref

NEG_INF = _core.NEG_INF

_DEFAULT_BACKEND = "xla"


@dataclass(frozen=True)
class PagedReadConfig:
    """THE paged/dense cache-read tuning knob (one documented home for
    thresholds that used to be scattered magic numbers).

    ``densify_elems``: the xla backend densifies a gather/ref attention
    problem whenever ``Lq * Lk <= densify_elems`` (one O(Lq·Lk) mask is
    cheaper than a chunk scan at that size); above it the online-softmax
    chunk stream keeps compiled memory O(Lq · chunk).

    ``chunk_tokens``: the decode-path default KV chunk width (tokens).
    Both the dense and the paged chunk streams clamp it to the live cache
    extent before padding — a short pool is never padded UP to the group
    width (the dense path got this clamp in PR 2; the paged group loop
    clamps to ``P' * page_size`` the same way)."""

    densify_elems: int = 256 * 256
    chunk_tokens: int = 2048


PAGED_READ = PagedReadConfig()


def set_default_backend(name: str) -> None:
    global _DEFAULT_BACKEND
    assert name in ("xla", "pallas", "ref")
    _DEFAULT_BACKEND = name


def default_backend() -> str:
    return _DEFAULT_BACKEND


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
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
    backend: Optional[str] = None,
    chunk: int = 512,
) -> jnp.ndarray:
    """FedAttn-aware multi-head attention. Shapes as attention_ref; the
    position/segment vectors may be per batch row (2-D) — continuous-batching
    decode against a slot pool, coalesced multi-request admission prefill —
    which ALL backends support through the shared attention core
    (repro.kernels.core): ref/xla broadcast the (Bm, Lq, Lk) mask, the
    Pallas kernel prefetches per-row vector blocks via its index maps."""
    backend = backend or _DEFAULT_BACKEND
    if backend == "ref" or (
        backend == "xla"
        and q.shape[1] * k.shape[1] <= PAGED_READ.densify_elems
    ):
        return _ref.attention_ref(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            causal=causal, local_only=local_only, contributed=contributed,
            window=window, soft_cap=soft_cap, sm_scale=sm_scale,
        )
    if backend == "pallas":
        from repro.kernels import flash_attention as fa

        return fa.flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            causal=causal, local_only=local_only, contributed=contributed,
            window=window, soft_cap=soft_cap, sm_scale=sm_scale,
        )
    return _chunked_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
        causal=causal, local_only=local_only, contributed=contributed,
        window=window, soft_cap=soft_cap, sm_scale=sm_scale, chunk=chunk,
    )


def _chunked_attention(
    q, k, v, *, q_pos, kv_pos, q_seg, kv_seg, causal, local_only,
    contributed, window, soft_cap, sm_scale, chunk,
):
    """Online-softmax attention, scanned over KV chunks. O(Lq·chunk) memory.

    The KV sequence is padded to a multiple of ``chunk``; padded slots carry
    kv_pos = +inf-like sentinel (and kv_seg = -2) so the masks remove them.
    ``chunk`` is clamped to Lk first — otherwise a short KV (e.g. a 128-slot
    decode cache under the decode default chunk=2048) would be padded up to
    a full chunk, wasting 16x the attention FLOPs/memory on masked slots.

    Position/segment vectors may be per batch row (2-D); padding and chunk
    slicing then run along the last axis via the shared
    :class:`repro.kernels.core.AttnSpec` (``pad_kv``/``chunk_kv``) and the
    per-chunk mask carries a batch dim (kernels.core.visibility).
    """
    B, Lq, nq, dh = q.shape
    _, Lk, nkv, _ = k.shape
    scale = sm_scale if sm_scale is not None else dh**-0.5

    spec = _core.AttnSpec(
        q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
        contributed=contributed, causal=causal, local_only=local_only,
        window=window, soft_cap=soft_cap, sm_scale=sm_scale,
    )
    chunk = max(1, min(chunk, Lk))
    pad = (-Lk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        spec = spec.pad_kv(pad)
    assert k.shape[1] == Lk + pad and pad < chunk, (
        f"over-padded KV: Lk={Lk} chunk={chunk} padded={k.shape[1]}"
    )
    n_chunks = (Lk + pad) // chunk
    sl = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, axis=1)
    fetch = lambda i: (sl(k, i), sl(v, i))
    return _online_attention(
        q, fetch, spec, chunk, n_chunks, nkv=nkv, soft_cap=soft_cap, scale=scale
    )


def _online_attention(q, fetch, spec, chunk, n_chunks, *, nkv, soft_cap, scale):
    """Online-softmax (flash-style) accumulation over KV chunks. ``fetch(i)``
    supplies chunk ``i``'s (kc, vc) — a dynamic slice of a dense cache or a
    page-group gather from a paged pool; the math is identical, so paged and
    dense attention agree bitwise wherever their masks agree. Each chunk
    runs the core's grouped-query contraction (``kernels.core.
    grouped_scores``/``grouped_values``): the carry stays grouped,
    (B, nkv, g, Lq) stats and (B, Lq, nkv, g, dh) values, until the end."""
    B, Lq, nq, dh = q.shape
    g = nq // nkv
    qg = _core.grouped_query(q, nkv, scale)

    def body(carry, i):
        m, l, acc = carry  # (B,nkv,g,Lq), (B,nkv,g,Lq), (B,Lq,nkv,g,dh)
        kc, vc = fetch(i)
        mask = spec.chunk_kv(i * chunk, chunk).mask()  # (Bm, Lq, chunk)
        s = _core.grouped_scores(qg, kc, mask, soft_cap)  # (B,nkv,g,Lq,chunk)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = _core.grouped_probs(s, m_new, mask)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc = acc * jnp.moveaxis(corr, -1, 1)[..., None] + _core.grouped_values(
            p, vc
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, nkv, g, Lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nkv, g, Lq), jnp.float32)
    acc0 = jnp.zeros((B, Lq, nkv, g, dh), jnp.float32)
    from repro.kernels.probe import probe_mode

    if probe_mode():
        # cost-probe: unrolled loop so cost_analysis counts every chunk
        carry = (m0, l0, acc0)
        for i in range(n_chunks):
            carry, _ = body(carry, jnp.asarray(i))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(n_chunks))
    l = jnp.maximum(l, 1e-20)
    out = acc / jnp.moveaxis(l, -1, 1)[..., None]
    return out.reshape(B, Lq, nq, dh).astype(q.dtype)


def attention_masked(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
    *, soft_cap: Optional[float] = None, sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Attention with a caller-supplied (Lq, Lk) visibility mask — used for
    per-participant sync schedules (Fig. 8) where the mask is not expressible
    through the standard flag vocabulary. Small-scale (O(L^2)) path; the
    softmax body is the shared core's."""
    return _core.masked_attention(
        q, k, v, mask, soft_cap=soft_cap, sm_scale=sm_scale
    )


def decode_attention(
    q: jnp.ndarray,  # (B, S, nq, dh) with small S (usually 1)
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    **kw,
) -> jnp.ndarray:
    """Decode-step attention against a KV cache; same masking vocabulary.

    ``S > 1`` is the multi-query verify form (speculative decoding): row
    ``b`` queries positions ``frontier_b .. frontier_b + S - 1`` against a
    cache whose matching rows were written immediately before this call,
    with 2-D per-row ``q_pos``/``q_seg`` vectors per the kernels.core
    contract — intra-block causality falls out of the ordinary
    ``q_pos >= kv_pos`` rule, no speculative-specific masking exists."""
    kw.setdefault("chunk", PAGED_READ.chunk_tokens)
    return attention(q, k_cache, v_cache, **kw)


# ---------------------------------------------------------------------------
# Paged attention (block-paged KV pool, serving/paging.py conventions)
# ---------------------------------------------------------------------------


def _gather_pages(pool, pages, scales=None):
    """(num_pages, page_size, nkv, dh) pool + (B, P') tables → dense
    (B, P'*page_size, nkv, dh). Gather CLAMPS sentinel entries to the last
    physical page; callers mask those columns via kv_pos/kv_seg.

    ``scales`` (num_pages, nkv) marks a quantized pool: the codes dequantize
    to f32 INSIDE this gather (serving/quant.py contract), so every caller
    downstream sees the dense-dtype pool. Clamped sentinel columns dequant
    garbage like they gather garbage — the kv_pos mask hides both."""
    N, ps = pool.shape[0], pool.shape[1]
    B, Pp = pages.shape
    idx = jnp.minimum(pages, N - 1)
    out = jnp.take(pool, idx, axis=0)
    out = out.reshape(B, Pp * ps, pool.shape[2], pool.shape[3])
    if scales is None:
        return out
    from repro.serving import quant as _quant

    s = jnp.repeat(jnp.take(scales, idx, axis=0), ps, axis=1)  # (B, Pp*ps, nkv)
    return _quant.dequantize(out, s)


def paged_attention(
    q: jnp.ndarray,  # (B, S, nq, dh)
    pk: jnp.ndarray,  # (num_pages, page_size, nkv, dh) — shared pool
    pv: jnp.ndarray,
    pages: jnp.ndarray,  # (B, P') int32 page tables; entries >= num_pages are holes
    *,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,  # (P'*ps,) or (B, P'*ps) linear positions
    q_seg: Optional[jnp.ndarray] = None,
    kv_seg: Optional[jnp.ndarray] = None,
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    backend: Optional[str] = None,
    chunk: int = 512,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """FedAttn attention reading KV through per-row page tables.

    Before any visibility decision, columns owned by sentinel table entries
    get ``kv_pos → PAD_POS`` (and ``kv_seg → KERNEL_PAD_SEGMENT``) so the
    standard mask vocabulary removes them — required because jnp gather
    clamps out-of-range page ids instead of dropping them. On the small /
    ref path the pool is densified per row and handed to :func:`attention`
    (same backend dispatch, hence bitwise parity with the dense pool); the
    large path gathers page groups chunk-by-chunk inside the online-softmax
    scan without ever materializing the dense (B, Lk) cache."""
    backend = backend or _DEFAULT_BACKEND
    N, ps = pk.shape[0], pk.shape[1]
    B, Pp = pages.shape
    Lk = Pp * ps
    col_valid = jnp.repeat(pages < N, ps, axis=1)  # (B, Lk)
    kv_pos = jnp.broadcast_to(jnp.atleast_2d(kv_pos), (B, Lk))
    kv_pos = jnp.where(col_valid, kv_pos, _core.PAD_POS)
    if kv_seg is not None:
        kv_seg = jnp.broadcast_to(jnp.atleast_2d(kv_seg), (B, Lk))
        kv_seg = jnp.where(col_valid, kv_seg, _core.KERNEL_PAD_SEGMENT)
    if backend != "xla" or q.shape[1] * Lk <= PAGED_READ.densify_elems:
        k = _gather_pages(pk, pages, k_scales)
        v = _gather_pages(pv, pages, v_scales)
        return attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            causal=causal, local_only=local_only, contributed=contributed,
            window=window, soft_cap=soft_cap, sm_scale=sm_scale,
            backend=backend,
        )
    return _chunked_paged_attention(
        q, pk, pv, pages, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg,
        kv_seg=kv_seg, causal=causal, local_only=local_only,
        contributed=contributed, window=window, soft_cap=soft_cap,
        sm_scale=sm_scale, chunk=chunk, k_scales=k_scales, v_scales=v_scales,
    )


def _chunked_paged_attention(
    q, pk, pv, pages, *, q_pos, kv_pos, q_seg, kv_seg, causal, local_only,
    contributed, window, soft_cap, sm_scale, chunk, k_scales=None,
    v_scales=None,
):
    """Online-softmax attention over page *groups*: each scan step gathers
    ``G = chunk // page_size`` pages from the pool and reuses the shared
    accumulation body (:func:`_online_attention`), so compiled memory is
    O(Lq · chunk) regardless of pool size. ``kv_pos``/``kv_seg`` arrive
    already per-row with sentinel columns masked (see paged_attention)."""
    from repro.serving import paging as _paging

    B, Lq, nq, dh = q.shape
    N, ps, nkv = pk.shape[0], pk.shape[1], pk.shape[2]
    Pp = pages.shape[1]
    scale = sm_scale if sm_scale is not None else dh**-0.5

    # clamp to the live pool extent FIRST — a short pool must never be
    # padded up to the group width (mirrors the dense chunk clamp)
    chunk = max(1, min(chunk, Pp * ps))
    G = max(1, min(_paging.pages_for(chunk, ps), Pp))
    chunk = G * ps
    padp = (-Pp) % G
    if padp:
        pages = jnp.pad(pages, ((0, 0), (0, padp)), constant_values=N)
        kv_pos = jnp.pad(
            kv_pos, ((0, 0), (0, padp * ps)), constant_values=_core.PAD_POS
        )
        if kv_seg is not None:
            kv_seg = jnp.pad(
                kv_seg, ((0, 0), (0, padp * ps)),
                constant_values=_core.KERNEL_PAD_SEGMENT,
            )
        if contributed is not None:
            pad_c = ((0, 0),) * (contributed.ndim - 1) + ((0, padp * ps),)
            contributed = jnp.pad(contributed, pad_c)
    n_groups = (Pp + padp) // G

    spec = _core.AttnSpec(
        q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
        contributed=contributed, causal=causal, local_only=local_only,
        window=window, soft_cap=soft_cap, sm_scale=sm_scale,
    )

    def fetch(i):
        pg = jax.lax.dynamic_slice_in_dim(pages, i * G, G, axis=1)  # (B, G)
        return (
            _gather_pages(pk, pg, k_scales),
            _gather_pages(pv, pg, v_scales),
        )

    return _online_attention(
        q, fetch, spec, chunk, n_groups, nkv=nkv, soft_cap=soft_cap, scale=scale
    )


def _paged_attention_with_mass(
    q, pk, pv, pages, *, q_pos, kv_pos, q_seg=None, kv_seg=None, causal=True,
    local_only=False, contributed=None, window=None, soft_cap=None,
    sm_scale=None, k_scales=None, v_scales=None,
):
    """XLA fallback for ``return_mass``: one densified
    ``masked_attention(return_stats=True, return_probs=True)`` pass yields
    both the normalized output and each pool column's normalized softmax
    mass (B, P'*ps) — the same quantity the fused kernel's stats emit, in
    the same stats vocabulary (core "Flash-decode rules")."""
    N, ps = pk.shape[0], pk.shape[1]
    B, Pp = pages.shape
    Lk = Pp * ps
    col_valid = jnp.repeat(pages < N, ps, axis=1)
    kv_pos = jnp.broadcast_to(jnp.atleast_2d(kv_pos), (B, Lk))
    kv_pos = jnp.where(col_valid, kv_pos, _core.PAD_POS)
    if kv_seg is not None:
        kv_seg = jnp.broadcast_to(jnp.atleast_2d(kv_seg), (B, Lk))
        kv_seg = jnp.where(col_valid, kv_seg, _core.KERNEL_PAD_SEGMENT)
    k = _gather_pages(pk, pages, k_scales)
    v = _gather_pages(pv, pages, v_scales)
    mask = _core.visibility(
        q_pos, kv_pos, q_seg, kv_seg, causal=causal, local_only=local_only,
        contributed=contributed, window=window,
    )
    m, l, acc, p = _core.masked_attention(
        q, k, v, mask, soft_cap=soft_cap, sm_scale=sm_scale,
        return_stats=True, return_probs=True,
    )
    denom = jnp.maximum(l, 1e-20)  # (B, nq, Lq)
    out = (acc / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    mass = jnp.sum(p / denom[..., None], axis=(1, 2))  # (B, Lk)
    return out, mass


def paged_decode_attention(
    q: jnp.ndarray,
    pk: jnp.ndarray,
    pv: jnp.ndarray,
    pages: jnp.ndarray,
    **kw,
) -> jnp.ndarray:
    """Decode-step attention through page tables; same masking vocabulary
    as :func:`decode_attention`, including its ``S > 1`` multi-query
    verify form — the page gather densifies (or chunk-streams) the pool
    and the block then sees exactly the dense verify semantics, so
    speculative paged decode is bitwise the dense-pool verify.

    ``backend='pallas'`` routes to the fused paged flash-decode kernel
    (kernels/flash_decode.py): in-kernel page loads + dequant-at-load +
    split-KV stats combine, never materializing the dense cache
    (interpret-mode on CPU, compiled on TPU). Split-KV softmax associates
    differently from the one-shot dense softmax, so the fused path agrees
    with the gather path to f32 rounding (greedy tokens exact on the
    pinned scheduler traces) rather than bitwise.

    ``return_mass=True`` additionally returns each pool column's
    normalized attention mass (B, P'*ps) — the ``'attnmass'``
    KV-selection accumulator feed — from the kernel's stats on the pallas
    path and from :func:`_paged_attention_with_mass` on the XLA path."""
    kw.setdefault("chunk", PAGED_READ.chunk_tokens)
    return_mass = kw.pop("return_mass", False)
    backend = kw.get("backend") or _DEFAULT_BACKEND
    if backend == "pallas":
        kw.pop("backend", None)
        kw.pop("chunk", None)
        from repro.kernels import flash_decode as _fd

        return _fd.paged_flash_decode(
            q, pk, pv, pages, return_mass=return_mass, **kw
        )
    if return_mass:
        kw.pop("backend", None)
        kw.pop("chunk", None)
        return _paged_attention_with_mass(q, pk, pv, pages, **kw)
    return paged_attention(q, pk, pv, pages, **kw)


# ---------------------------------------------------------------------------
# RWKV6 / Mamba
# ---------------------------------------------------------------------------


def _recurrent_kernel(name: str, initial_state) -> bool:
    """Whether ``backend='pallas'`` runs the chunked Pallas kernel for a
    recurrence. A state-carrying call (``initial_state`` given — the
    decode path) runs the reference scan: the chunked kernels start from a
    zero state. On a TPU the kernels' ``(1, chunk, 1, d)`` blocks are
    refused by the compiler (block dims must be whole or 8/128-aligned),
    so the pallas backend raises there, naming the cause."""
    if initial_state is not None:
        return False
    if not _core.pallas_interpret():
        raise NotImplementedError(
            f"the {name} Pallas kernel does not compile for the TPU yet; "
            "serve recurrent stacks with backend='xla' on a TPU"
        )
    return True


def rwkv6(
    r, k, v, w, u, *,
    initial_state=None, reset_mask=None, valid=None, backend=None,
):
    """WKV6 recurrence. ``reset_mask``/``valid`` may be shared 1-D ``(L,)``
    or per-row 2-D ``(B, L)`` — the recurrence half of the repo-wide vector
    contract (repro.kernels.core docstring): invalid tokens are identity
    state updates, so pow2-padded / ragged-row batches scan safely."""
    backend = backend or _DEFAULT_BACKEND
    if backend == "pallas" and _recurrent_kernel("rwkv6", initial_state):
        from repro.kernels import rwkv6 as _rk

        return _rk.rwkv6_chunked(
            r, k, v, w, u, reset_mask=reset_mask, valid=valid,
        )
    return _ref.rwkv6_ref(
        r, k, v, w, u,
        initial_state=initial_state, reset_mask=reset_mask, valid=valid,
    )


def mamba_scan(
    x, delta, A, Bm, C, D, *,
    initial_state=None, reset_mask=None, valid=None, backend=None,
):
    """Mamba1 selective scan; ``reset_mask``/``valid`` as in :func:`rwkv6`."""
    backend = backend or _DEFAULT_BACKEND
    if backend == "pallas" and _recurrent_kernel("mamba_scan", initial_state):
        from repro.kernels import mamba_scan as _ms

        return _ms.mamba_scan_chunked(
            x, delta, A, Bm, C, D, reset_mask=reset_mask, valid=valid,
        )
    return _ref.mamba_scan_ref(
        x, delta, A, Bm, C, D,
        initial_state=initial_state, reset_mask=reset_mask, valid=valid,
    )
