"""The FLOP count counts only the (query, key) pairs the FedAttn mask leaves
visible: checked against a brute-force count over the mask."""
import numpy as np
import pytest

from bench import flops
from bench.arch import qwen2
from bench.reference import positions_and_segments


def visible(T: int, L: int, n: int, sync: bool) -> np.ndarray:
    pos, seg = positions_and_segments(T, L, n)
    vis = pos[:, None] >= pos[None, :]
    if not sync:
        vis &= seg[:, None] == seg[None, :]
    return vis


@pytest.mark.parametrize("L,n", [(1, 1), (7, 3), (64, 4), (129, 8), (30, 7)])
def test_prefill_pairs_are_the_visible_ones(L, n):
    local, sync = flops.prefill_pairs(L, n)
    assert local == visible(L, L, n, False).sum()
    assert sync == visible(L, L, n, True).sum()
    assert sum(flops.segment_sizes(L, n)) == L


@pytest.mark.parametrize("L,n,lo,hi", [(10, 2, 0, 5), (33, 4, 3, 17), (64, 8, 0, 1)])
def test_decode_pairs_are_the_visible_ones(L, n, lo, hi):
    T = L + hi
    loc, syn = visible(T, L, n, False), visible(T, L, n, True)
    # token m is computed at the row of position L + m - 1
    rows = [L + m - 1 for m in range(lo + 1, hi + 1)]
    assert flops.decode_pairs(L, n, lo, hi) == (loc[rows].sum(), syn[rows].sum())


def test_flops_split_into_weights_attention_and_head():
    """Qwen2's count, taken layer by layer from its architecture module."""
    dm = qwen2.Dims(model_type="qwen2", n_layers=4, d=8, n_q=2, n_kv=1, d_head=4, d_ff=16,
                    vocab=10, vocab_rows=10, tied=False, eps=1e-6, theta=1e4,
                    dtype="float32", sync_interval=2, participants=2)
    L = 6
    per_token = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    local, sync = flops.prefill_pairs(L, 2)
    attn = 4 * 2 * 4 * (2 * local + 2 * sync)
    assert flops.prefill_flops(dm, L) == 4 * L * per_token + attn + 2 * 8 * 10
    dl, ds = flops.decode_pairs(L, 2, 0, 3)
    assert flops.decode_flops(dm, L, 0, 3) == (
        3 * (4 * per_token + 2 * 8 * 10) + 4 * 2 * 4 * (2 * dl + 2 * ds))
    assert flops.decode_flops(dm, L, 3, 3) == 0
