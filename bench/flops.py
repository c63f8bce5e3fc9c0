"""Model FLOPs of the work a window asked for, from the published widths.

Counted: every weight matrix once per real token (2 FLOPs per multiply-add),
attention's scores and weighted sum over the (query, key) pairs that the
FedAttn mask leaves visible, and the LM head once per sampled position.
Not counted: bucket padding, pool pages and tiles that the mask hides,
coasting sub-steps of finished slots, norms, RoPE and softmax.

Visibility (``kernels/core.tile_visibility`` in the program, written out
here): causal everywhere; a local layer's query sees keys of its own
participant only, a sync layer's query sees every earlier key. A prompt is
split over N participants by the contiguous partition (the remainder goes
to the last participants); generated tokens belong to the publisher, the
last participant.
"""
from __future__ import annotations

from bench.model import Dims


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


def _per_token(dm: Dims) -> int:
    q, kv = dm.n_q * dm.d_head, dm.n_kv * dm.d_head
    return 2 * (dm.d * q + 2 * dm.d * kv + q * dm.d + 3 * dm.d * dm.d_ff)


def _attn(dm: Dims, local_pairs: int, sync_pairs: int) -> int:
    n_sync = dm.n_layers // dm.sync_interval
    per_pair = 4 * dm.n_q * dm.d_head  # q.k and p.v
    return per_pair * ((dm.n_layers - n_sync) * local_pairs + n_sync * sync_pairs)


def prefill_flops(dm: Dims, L: int) -> int:
    """Prefill of one prompt, with its first sampled position."""
    return (dm.n_layers * L * _per_token(dm)
            + _attn(dm, *prefill_pairs(L, dm.participants))
            + 2 * dm.d * dm.vocab)


def decode_flops(dm: Dims, L: int, m_lo: int, m_hi: int) -> int:
    """The decode steps producing generated tokens m_lo < m <= m_hi (token
    0 comes from the prefill)."""
    k = m_hi - m_lo
    if k <= 0:
        return 0
    return (k * (dm.n_layers * _per_token(dm) + 2 * dm.d * dm.vocab)
            + _attn(dm, *decode_pairs(L, dm.participants, m_lo, m_hi)))
