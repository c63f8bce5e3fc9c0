"""The shared attention core: ONE visibility/masking spec for every path.

The paper's protocol is a single masking rule (Phase-I local attention,
eq. 18; Phase-II global attention over the exchanged KV, eqs. 20-21; sparse
contribution masks, eq. 37; optional sliding windows), but a serving stack
grows many attention *implementations* — the pure-jnp oracle
(:mod:`repro.kernels.ref`), the chunked online-softmax XLA path
(:mod:`repro.kernels.ops`), the Pallas flash kernel
(:mod:`repro.kernels.flash_attention`), and the shard_map SPMD realization
(:mod:`repro.distributed.spmd_attention`). This module is the one place the
masking rule and the softmax accumulation live; every implementation above
composes these primitives instead of re-deriving them.

Vector contract (THE reference for the whole repo)
--------------------------------------------------
``visibility``/:class:`AttnSpec` accept every position/segment/contribution
vector either

* **1-D** ``(L,)`` — shared across the batch (classic prefill/decode: all
  rows sit at the same offsets under the same partition), or
* **2-D** ``(B, L)`` — per batch row (continuous-batching decode over a KV
  slot pool, coalesced multi-request admission prefill: every row has its
  own write frontier, partition and padding). Mixing is fine; the mask's
  leading dim broadcasts to ``Bm = max`` of the leading dims (1 when
  everything is shared).

Sentinels: ``kv_pos == int32 max`` (kernel chunk/block padding) and
``kv_seg < 0`` (shape-bucketing pads with ``-1``, kernels pad with ``-2``,
inactive pool slots carry ``-1``) are never visible to any query.

Recurrence validity rules (the contract's second half)
------------------------------------------------------
The recurrent layers (mamba/rwkv — :mod:`repro.models.ssm`,
:mod:`repro.kernels.mamba_scan`, :mod:`repro.kernels.rwkv6`) consume the
SAME segment vectors, 1-D shared or 2-D per-row, but cannot "mask" a token
out of a scan the way attention drops a column — instead a sentinel token
(segment ``< 0``) becomes an **identity state update**:

* **mamba** — Δ·mask gating: ``Δ ← where(valid, Δ, 0)`` gives decay
  ``exp(0·A) = 1`` and zero input injection, so ``h_t = h_{t-1}`` exactly.
* **rwkv6** — decay/k masking: ``w ← 0`` (decay ``e^0 = 1``) and ``k ← 0``
  (zero kv outer product), so ``S_t = S_{t-1}`` exactly.
* **token-shift / causal-conv windows** — positional carries come from the
  last ``width`` *valid* tokens (``models.layers.carry_window``), never
  the padded suffix; a fully-invalid row keeps its incoming carry.
* **segment resets** (FedAttn-local scans) generalize 1-D → 2-D per-row
  alongside, and are suppressed at invalid positions — a reset at the pad
  boundary would zero the state the padding must preserve.

The identities are exact in float32 (``x·1`` and ``x+0`` are bitwise), so
a pow2-padded suffix — or a padded row of a ragged coalesced-admission
batch — leaves recurrent state and valid-token outputs bit-identical to
the unpadded scan (pinned in tests/test_ssm_masking.py). This is what lets
the serving engine L-bucket SSM/hybrid stacks and the scheduler run ONE
coalesced admission path for every stack kind.

Page tables and visibility (the paged KV pool)
----------------------------------------------
The block-paged pool (:mod:`repro.serving.paging`,
``models.transformer.init_paged_cache``) stores KV in a per-layer
``(num_pages, page_size, ...)`` physical pool; each slot reaches its rows
through a traced int32 page table. The interaction rules with this
contract:

* **Page tables are DATA, never shapes.** They enter jitted entry points
  as traced arguments, so admission/retirement rewrites them without
  recompiling — the same zero-churn guarantee the dense pool pins.
* **Gather first, then the one masking rule.** Paged readers
  (``kernels.ops.paged_attention`` / ``paged_decode_attention``,
  ``distributed.spmd_attention.paged_decode_attention``) gather pages
  into the dense ``(B, capacity)`` layout and hand the SAME
  ``kv_pos``/``kv_seg`` vectors to this module — visibility is decided
  by position/segment exactly as for dense rows, NEVER by page identity.
  A page being mapped does not make its rows visible; rows past a slot's
  frontier still carry ``kv_pos == PAD_POS``/``kv_seg < 0``.
* **The sentinel page id is ``num_pages``.** Unmapped table entries point
  one past the pool; gathers clamp the index and the ``PAD_POS`` rule
  masks the result, scatters drop out-of-range writes
  (``mode="drop"``) — so a sentinel entry is exactly "no rows here".
* **Shared pages are immutable.** A prefix-cache hit maps cached pages
  (refcounted) into a new slot's table; writes only ever target pages the
  slot owns solely — a shared partially-filled boundary page is
  copied-on-write before the suffix lands. Page arithmetic (which page,
  which offset) lives ONLY in :mod:`repro.serving.paging` (lint rule
  FED006).

Quantization rules (the int8/fp8 paged pool)
--------------------------------------------
A quantized pool (:mod:`repro.serving.quant`) stores the same pages as
codes plus sibling per-page-per-kv-head f32 scale leaves. Three rules
keep it invisible to this contract:

* **Dequant at gather.** Codes meet scales ONLY inside the paged readers'
  page gather (and the SPMD in-shard take) — by the time rows reach this
  module's masking rule they are the dense compute dtype. No kernel, mask
  or sentinel ever branches on the storage dtype.
* **Visibility is NEVER decided by quantized values.** Position/segment
  vectors and page tables stay unquantized int32; a page's scale (even
  0.0 on an all-zero page) says nothing about which of its rows are
  visible — the ``PAD_POS``/segment rules above are unchanged.
* **Scales are DATA, not shapes.** They ride the cache pytree next to
  the page tables and rewrite freely under churn (scatter-max at the
  frontier, reset on admission) — the zero-recompile pin holds
  (jaxpr-audited: ``analysis.jaxpr_audit.audit_quant_pool`` also proves
  the pool buffers are actually int8/fp8 in the compiled step). Scale
  arithmetic lives ONLY in :mod:`repro.serving.quant` (lint rule
  FED007).

Multi-token verify (speculative decoding)
-----------------------------------------
The scheduler's speculative verify step (``serving/scheduler._verify_fn``)
is a plain instance of the 2-D vector contract — no new mask logic. Each
pool slot ``b`` queries ``k+1`` positions spanning its write frontier:
``positions[b] = frontier_b .. frontier_b + k`` (the last accepted token
plus ``k`` draft candidates), with its publisher segment broadcast across
the block. Visibility rules as they apply to that block:

* **Within the block, causality orders the drafts.** Draft row ``i`` is
  visible to draft queries ``> i`` of the same slot (its KV is written
  before the block attends — the decode-layer contract) and hidden from
  queries ``<= i`` by the ordinary ``q_pos >= kv_pos`` rule. That is
  exactly the sequential decode's view, which is why accepted tokens are
  bitwise those of non-speculative decode.
* **Draft rows past the accept point are never visible afterwards.** The
  scheduler advances the frontier by ``accept+1``, so the NEXT verify
  block's write span ``[frontier', frontier'+k]`` starts at (covers) every
  rejected row and overwrites it before any query can look that far;
  causality hides rows beyond the live write span in the meantime, and a
  retiring slot's whole row set drops behind the ``PAD_SEGMENT``
  kv-segment sentinel (inactive slots are invisible, including to
  themselves). No scrub pass, no new sentinel — the existing
  segment-sentinel contract is the invalidation mechanism.
* **Other slots never see draft rows at all** (segment masking between
  slots is unchanged); under the paged pool draft rows land in pages the
  slot owns solely — speculative headroom is allocated at admission
  (``serving.paging.pages_for_request``) precisely so a draft write never
  targets a shared or unmapped page.

``publisher_lo`` is the decode-time alternative to segment masking used by
the sequence-sharded SPMD cache (flash-decoding): at a local (non-sync)
layer only cache rows with ``kv_pos >= publisher_lo`` — the publisher's own
segment plus every generated token — are visible. It is equivalent to
``local_only`` segment masking whenever the publisher owns the trailing
contiguous segment (the repo-wide convention); pass segments instead when
per-row partitions make that assumption unsafe.

Flash-decode rules (fused paged pooled step)
--------------------------------------------
The fused Pallas paged flash-decode (``kernels/flash_decode.py``) splits
the pooled step over page blocks and re-reduces. The rules it rides:

* **Split-KV stats combine is THE core stats vocabulary.** Each page
  program emits the partial ``(m, l, acc)`` triple of
  ``masked_attention(return_stats=True)`` for its block; the combine —
  global max, ``exp(m - m_g)`` correction, sum — is *the same reduction*
  ``distributed/spmd_attention`` applies across shards with
  ``pmax``/``psum``. Shard-local kernel + existing collective combine is
  therefore the whole SPMD story; no kernel ever normalizes early.
* **Visibility is never decided in-kernel by page identity.** Sentinel /
  hole table entries are resolved BEFORE the kernel runs: their columns'
  ``kv_pos``/``kv_seg`` are forced to ``PAD_POS``/``KERNEL_PAD_SEGMENT``
  and the block load merely clamps the page index (gathers clamp, masks
  hide). Inside the kernel only :func:`visibility` — fed those sentinel
  rows — decides what a query sees, so the mask logic cannot fork.
* **Dequant-at-load keeps the dense f32 contract downstream.** Quantized
  pools enter the kernel as codes plus per-page-per-head scale operands
  block-indexed by the *same* resolved page; ``serving.quant.dequantize``
  applies ``code * scale`` at load and everything after the load — scores,
  stats, combine — is ordinary dense f32. Scale *arithmetic* (amax,
  rescale, codec choice) never enters a kernel (FED007).
* **Attention mass is a stats by-product, not a second pass.** The masked
  softmax numerators ``p`` the stats form already computes, rebased by the
  same ``exp(m - m_g)`` correction and normalized by ``l_g``, are the
  per-column attention mass the ``'attnmass'`` KV-selection policy
  consumes — ``masked_attention(..., return_probs=True)`` is the XLA
  fallback's spelling of the same thing.

This contract is *mechanically enforced*: :mod:`repro.analysis` lints the
tree against private mask/sentinel copies (rules FED001/FED002) and
jaxpr-audits every jitted serving entry point — see README.md,
"Static analysis & enforced invariants", for the rule table and the
escape-hatch policy.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp

# THE repo-wide sentinel scheme. Bare ``-1``/``-2`` segment literals and
# private NEG_INF copies outside this module are rejected by the invariant
# analyzer (``python -m repro.analysis`` — rules FED001/FED002); always
# name these constants.
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
PAD_POS = jnp.iinfo(jnp.int32).max  # padded KV slot position sentinel
PAD_SEGMENT = -1  # shape-bucketing / inactive-pool-slot segment sentinel
KERNEL_PAD_SEGMENT = -2  # kernel-internal chunk/block padding sentinel


def _as2(a: jnp.ndarray) -> jnp.ndarray:
    return a if a.ndim == 2 else a[None]


def as_row_mask(m: Optional[jnp.ndarray], L: int) -> Optional[jnp.ndarray]:
    """Normalize a per-token validity/reset mask to ``(B-or-1, L)`` — the
    1-D shared / 2-D per-row vector contract (module docstring). The ONE
    normalizer for the recurrence kernels (ref + Pallas wrappers)."""
    if m is None:
        return None
    m2 = _as2(m)
    assert m2.shape[-1] == L, f"mask length {m2.shape} != scan length {L}"
    return m2


def as_reset_rows(reset_mask: Optional[jnp.ndarray], B: int, L: int) -> jnp.ndarray:
    """Reset mask as a dense ``(B, L)`` int32 tensor — the form the Pallas
    recurrence kernels take as a block input (None → all zeros). Shared by
    the mamba/rwkv chunked wrappers so the reset convention has one point
    of change."""
    m2 = as_row_mask(reset_mask, L)
    if m2 is None:
        return jnp.zeros((B, L), jnp.int32)
    return jnp.broadcast_to(m2.astype(jnp.int32), (B, L))


def visibility(
    q_pos: jnp.ndarray,  # (Lq,) or (B, Lq)
    kv_pos: jnp.ndarray,  # (Lk,) or (B, Lk)
    q_seg: Optional[jnp.ndarray] = None,  # (Lq,) or (B, Lq)
    kv_seg: Optional[jnp.ndarray] = None,  # (Lk,) or (B, Lk)
    *,
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,  # (Lk,) or (B, Lk)
    window: Optional[int] = None,
    publisher_lo=None,  # int / scalar / (B,) — decode rule, see module doc
) -> jnp.ndarray:
    """FedAttn visibility as a ``(Bm, Lq, Lk)`` bool mask.

    The ONE mask constructor of the repo (module docstring has the 1-D/2-D
    vector contract and the sentinel conventions). Rules, in order:

    * ``causal``: ``q_pos >= kv_pos``; bidirectional drops only the
      position-sentinel padded rows.
    * ``window``: relative-position sliding window on top.
    * ``publisher_lo``: decode-time publisher rule (SPMD sharded cache).
    * segments (when both given): padded rows (``kv_seg < 0``) are never
      visible; ``local_only`` restricts to the segment diagonal (Phase I,
      eq. 18); otherwise ``contributed`` thins the off-diagonal to the
      exchanged rows (Phase II, eqs. 20-21 / 37).
    """
    col = lambda a: None if a is None else _as2(a)[:, :, None]
    row = lambda a: None if a is None else _as2(a)[:, None, :]
    if publisher_lo is not None:
        publisher_lo = jnp.asarray(publisher_lo).reshape((-1, 1, 1))
    use_seg = q_seg is not None and kv_seg is not None
    return tile_visibility(
        col(q_pos), row(kv_pos),
        col(q_seg) if use_seg else None, row(kv_seg) if use_seg else None,
        causal=causal, local_only=local_only, contributed=row(contributed),
        window=window, publisher_lo=publisher_lo,
    )


def tile_visibility(
    q_pos: jnp.ndarray,  # (..., Lq, 1) query column
    kv_pos: jnp.ndarray,  # (..., 1, Lk) key row
    q_seg: Optional[jnp.ndarray] = None,
    kv_seg: Optional[jnp.ndarray] = None,
    *,
    causal: bool = True,
    local_only: bool = False,
    contributed: Optional[jnp.ndarray] = None,  # (..., 1, Lk)
    window: Optional[int] = None,
    publisher_lo=None,  # static int or an array broadcastable to the mask
) -> jnp.ndarray:
    """The rule body of :func:`visibility` on operands that are already
    broadcast-shaped: query-side vectors as columns, key-side vectors as
    rows. The mask is their ordinary broadcast — a compiled Pallas kernel
    calls this on its ``(rows, 1)`` / ``(1, cols)`` tiles (2-D, no
    lane-to-sublane relayout), :func:`visibility` on ``(B, L, 1)`` /
    ``(B, 1, L)`` views of the vector contract."""
    if causal:
        mask = q_pos >= kv_pos
    else:
        mask = jnp.broadcast_to(
            kv_pos < PAD_POS, jnp.broadcast_shapes(q_pos.shape, kv_pos.shape)
        )
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    if publisher_lo is not None:
        mask &= kv_pos >= publisher_lo
    if q_seg is not None and kv_seg is not None:
        mask &= kv_seg >= 0
        same = q_seg == kv_seg
        if local_only:
            mask &= same
        elif contributed is not None:
            mask &= same | contributed
    return mask


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """THE interpret rule of every Pallas kernel in the repo: compiled on a
    TPU, run by the JAX interpreter anywhere else (CPU tests). An explicit
    ``interpret`` wins — a test compiling for a described TPU from a CPU
    process passes ``False``."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@dataclass(frozen=True)
class AttnSpec:
    """Everything that determines attention visibility + logit shaping, in
    one carrier: the static flags (``causal``/``local_only``/``window``/
    ``soft_cap``/``sm_scale``/``publisher_lo``) plus the position/segment/
    contribution operands (each 1-D shared or 2-D per-row — module doc).

    ``pad_kv``/``chunk_kv`` produce derived specs whose KV-side operands are
    padded with the repo sentinels / sliced to one KV chunk — the chunked
    and blocked implementations iterate these instead of re-implementing
    sentinel bookkeeping.
    """

    q_pos: jnp.ndarray
    kv_pos: jnp.ndarray
    q_seg: Optional[jnp.ndarray] = None
    kv_seg: Optional[jnp.ndarray] = None
    contributed: Optional[jnp.ndarray] = None
    causal: bool = True
    local_only: bool = False
    window: Optional[int] = None
    soft_cap: Optional[float] = None
    sm_scale: Optional[float] = None
    publisher_lo: Optional[int | jnp.ndarray] = None

    def scale(self, head_dim: int) -> float:
        return self.sm_scale if self.sm_scale is not None else head_dim**-0.5

    def mask(self) -> jnp.ndarray:
        """(Bm, Lq, Lk) visibility of this spec (see :func:`visibility`)."""
        return visibility(
            self.q_pos, self.kv_pos, self.q_seg, self.kv_seg,
            causal=self.causal, local_only=self.local_only,
            contributed=self.contributed, window=self.window,
            publisher_lo=self.publisher_lo,
        )

    def pad_kv(self, pad: int) -> "AttnSpec":
        """Spec with KV-side operands padded by ``pad`` sentinel slots."""
        if pad == 0:
            return self
        last = lambda a, val: jnp.pad(
            a, [(0, 0)] * (a.ndim - 1) + [(0, pad)], constant_values=val
        )
        return replace(
            self,
            kv_pos=last(self.kv_pos, PAD_POS),
            kv_seg=None if self.kv_seg is None else last(self.kv_seg, KERNEL_PAD_SEGMENT),
            contributed=(
                None if self.contributed is None else last(self.contributed, False)
            ),
        )

    def chunk_kv(self, start, size: int) -> "AttnSpec":
        """Spec restricted to KV slots ``[start, start + size)`` (``start``
        may be traced — chunked/blocked inner loops)."""
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, axis=a.ndim - 1)
        return replace(
            self,
            kv_pos=sl(self.kv_pos),
            kv_seg=None if self.kv_seg is None else sl(self.kv_seg),
            contributed=None if self.contributed is None else sl(self.contributed),
        )


# Grouped-query attention. Query head ``h = kv·g + j`` (``g = nq // nkv``)
# reads kv head ``kv``: the bodies below view q as (B, Lq, nkv, g, dh) and
# contract it against K/V at their own nkv heads, so no K/V copy at nq
# heads exists (g = 1 is plain multi-head attention). Every f32 contraction
# asks for HIGHEST precision: a grouped dot has g·Lq rows, which XLA on a
# TPU would otherwise round to a single bf16 pass.
_HIGHEST = jax.lax.Precision.HIGHEST


def grouped_query(q: jnp.ndarray, nkv: int, scale: float) -> jnp.ndarray:
    """(B, Lq, nq, dh) → f32 (B, Lq, nkv, g, dh), scaled; head ``kv·g + j``
    lands at ``[:, :, kv, j]``."""
    B, Lq, nq, dh = q.shape
    return (q.astype(jnp.float32) * scale).reshape(B, Lq, nkv, nq // nkv, dh)


def grouped_scores(
    qg: jnp.ndarray,  # (B, Lq, nkv, g, dh) f32, from grouped_query
    k: jnp.ndarray,  # (B, Lk, nkv, dh)
    mask: jnp.ndarray,  # (Bm, Lq, Lk)
    soft_cap: Optional[float] = None,
) -> jnp.ndarray:
    """f32 logits (B, nkv, g, Lq, Lk), soft-capped, invisible columns at
    ``NEG_INF``."""
    s = jnp.einsum(
        "bqkgd,btkd->bkgqt", qg, k.astype(jnp.float32), precision=_HIGHEST
    )
    if soft_cap:
        s = jnp.tanh(s / soft_cap) * soft_cap
    return jnp.where(mask[:, None, None], s, NEG_INF)


def grouped_probs(s: jnp.ndarray, m: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Softmax numerators ``exp(s - m)`` of :func:`grouped_scores` logits
    against the row max ``m`` (B, nkv, g, Lq), re-masked to exact zeros (a
    fully-masked row has ``m = NEG_INF`` and would read ``exp(0)``)."""
    return jnp.where(mask[:, None, None], jnp.exp(s - m[..., None]), 0.0)


def grouped_values(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``p`` (B, nkv, g, Lq, Lk) · ``v`` (B, Lk, nkv, dh) → f32
    (B, Lq, nkv, g, dh)."""
    return jnp.einsum(
        "bkgqt,btkd->bqkgd", p, v.astype(jnp.float32), precision=_HIGHEST
    )


def masked_attention(
    q: jnp.ndarray,  # (B, Lq, nq, dh)
    k: jnp.ndarray,  # (B, Lk, nkv, dh)
    v: jnp.ndarray,
    mask: jnp.ndarray,  # (Lq, Lk) or (Bm, Lq, Lk), Bm ∈ {1, B}
    *,
    soft_cap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    return_stats: bool = False,
    return_probs: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, ...]:
    """The ONE masked-softmax attention body (f32 accumulation).

    GQA is grouped, not broadcast: query head ``h = kv·g + j`` is contracted
    against kv head ``kv`` of ``k``/``v`` at their own ``nkv`` heads
    (:func:`grouped_scores`, :func:`grouped_values`); every output below is
    in the per-query-head layout, heads in that order.

    With ``return_stats`` it returns the partial-softmax statistics
    ``(m, l, acc)`` — ``m`` (B, nq, Lq) running max, ``l`` row mass, ``acc``
    (B, Lq, nq, dh) unnormalized value sum — the flash-decoding combinable
    form: shards compute stats over their KV slice and a pmax/psum merge
    reproduces the full softmax exactly (distributed/spmd_attention.py).
    ``return_probs`` (stats form only) appends ``p`` (B, nq, Lq, Lk), the
    masked softmax numerators relative to ``m`` — the per-column
    attention-mass ingredient the ``'attnmass'`` KV-selection wiring
    consumes (see the "Flash-decode rules" contract section).
    Fully-masked rows yield zero output (l = 0 guarded), never NaN.
    """
    B, Lq, nq, dh = q.shape
    scale = sm_scale if sm_scale is not None else dh**-0.5
    if mask.ndim == 2:
        mask = mask[None]
    qg = grouped_query(q, k.shape[2], scale)
    s = grouped_scores(qg, k, mask, soft_cap)  # (B, nkv, g, Lq, Lk)
    m = jnp.max(s, axis=-1)
    p = grouped_probs(s, m, mask)
    l = jnp.sum(p, axis=-1).reshape(B, nq, Lq)
    m = m.reshape(B, nq, Lq)
    acc = grouped_values(p, v).reshape(B, Lq, nq, dh)
    if return_stats:
        if return_probs:
            return m, l, acc, p.reshape(B, nq, Lq, -1)
        return m, l, acc
    out = acc / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
