"""Model FLOPs of the work a window asked for, from the published widths.

Counted: every weight matrix once per real token (2 FLOPs per multiply-add),
attention's scores and weighted sum over the (query, key) pairs that the
FedAttn mask leaves visible, and the LM head once per sampled position.
Not counted: bucket padding, pool pages and tiles that the mask hides,
coasting sub-steps of finished slots, norms, RoPE and softmax.

What a layer costs is the architecture module's (``bench/model.py``):
``weight_flops`` per token and ``pair_flops`` per visible pair, layer by
layer. A module whose mask is not the plain FedAttn one gives its own
``prefill_flops`` and ``decode_flops``, which take the place of those here.

Visibility (``kernels/core.tile_visibility`` in the program,
``reference.visible`` in the benchmark): causal everywhere; a local
layer's query sees keys of its own participant only, a sync layer's query
sees every earlier key. A prompt is split over N participants by the
contiguous partition (the remainder goes to the last participants);
generated tokens belong to the publisher, the last participant.
"""
from __future__ import annotations

import functools

from bench.model import Dims, arch


def segment_sizes(L: int, n: int) -> list[int]:
    base, rem = divmod(L, n)
    return [base + (1 if i >= n - rem else 0) for i in range(n)]


def prefill_pairs(L: int, n: int) -> tuple[int, int]:
    """Visible (query, key) pairs of a prompt of L tokens in one local and
    in one sync layer."""
    local = sum(s * (s + 1) // 2 for s in segment_sizes(L, n))
    return local, L * (L + 1) // 2


def decode_pairs(L: int, n: int, m_lo: int, m_hi: int) -> tuple[int, int]:
    """Visible pairs, summed over generated tokens m_lo < m <= m_hi, of the
    decode steps that produce them: token m is computed from the token at
    position L + m - 1, which sees the publisher's segment and the m
    generated positions in a local layer, and L + m keys in a sync layer."""
    pub = segment_sizes(L, n)[-1]
    k = m_hi - m_lo
    s_m = (m_hi * (m_hi + 1) - m_lo * (m_lo + 1)) // 2
    return k * pub + s_m, k * L + s_m


@functools.lru_cache(maxsize=None)
def _terms(dm: Dims) -> tuple[int, int, int]:
    """(weight FLOPs per token over all layers, attention FLOPs per visible
    pair over the local layers, over the sync layers), from the
    architecture module's per-layer counts."""
    a = arch(dm.model_type)
    per_pair = [(a.pair_flops(dm, i), dm.is_sync(i)) for i in range(dm.n_layers)]
    return (sum(a.weight_flops(dm, i) for i in range(dm.n_layers)),
            sum(f for f, sync in per_pair if not sync),
            sum(f for f, sync in per_pair if sync))


def _head(dm: Dims) -> int:
    return 2 * dm.d * dm.vocab


def prefill_flops(dm: Dims, L: int) -> int:
    """Prefill of one prompt, with its first sampled position."""
    own = getattr(arch(dm.model_type), "prefill_flops", None)
    if own is not None:
        return own(dm, L)
    weights, local, sync = _terms(dm)
    local_pairs, sync_pairs = prefill_pairs(L, dm.participants)
    return L * weights + local * local_pairs + sync * sync_pairs + _head(dm)


def decode_flops(dm: Dims, L: int, m_lo: int, m_hi: int) -> int:
    """The decode steps producing generated tokens m_lo < m <= m_hi (token
    0 comes from the prefill)."""
    own = getattr(arch(dm.model_type), "decode_flops", None)
    if own is not None:
        return own(dm, L, m_lo, m_hi)
    k = m_hi - m_lo
    if k <= 0:
        return 0
    weights, local, sync = _terms(dm)
    local_pairs, sync_pairs = decode_pairs(L, dm.participants, m_lo, m_hi)
    return k * (weights + _head(dm)) + local * local_pairs + sync * sync_pairs
