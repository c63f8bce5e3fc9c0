"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode — the kernel body executes on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan_chunked
from repro.kernels.rwkv6 import rwkv6_chunked

TOLS = {jnp.float32: 3e-5, jnp.bfloat16: 3e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Lq,Lk,nq,nkv,dh",
    [
        (1, 64, 64, 4, 4, 32),  # MHA, aligned
        (2, 100, 100, 4, 2, 64),  # GQA, unaligned length (padding path)
        (1, 33, 129, 8, 1, 64),  # MQA, Lq != Lk
    ],
)
def test_flash_shapes_dtypes(B, Lq, Lk, nq, nkv, dh, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Lq, nq, dh), dtype)
    k = jax.random.normal(ks[1], (B, Lk, nkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, Lk, nkv, dh), dtype)
    q_pos = jnp.arange(Lk - Lq, Lk)  # decode-suffix style positions
    kv_pos = jnp.arange(Lk)
    out = flash_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, block_q=32, block_k=32
    )
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype],
    )


@pytest.mark.parametrize("mode", ["local", "sparse", "window", "softcap", "bidir"])
def test_flash_fedattn_masks(mode):
    B, Lq, nq, nkv, dh = 2, 96, 4, 2, 32
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (B, Lq, nq, dh))
    k = jax.random.normal(ks[1], (B, Lq, nkv, dh))
    v = jax.random.normal(ks[2], (B, Lq, nkv, dh))
    pos = jnp.arange(Lq)
    seg = jnp.repeat(jnp.arange(4), 24)
    kw = dict(q_pos=pos, kv_pos=pos)
    if mode == "local":
        kw.update(q_seg=seg, kv_seg=seg, local_only=True)
    elif mode == "sparse":
        kw.update(q_seg=seg, kv_seg=seg,
                  contributed=jax.random.bernoulli(ks[3], 0.25, (Lq,)))
    elif mode == "window":
        kw.update(window=17)
    elif mode == "softcap":
        kw.update(soft_cap=20.0)
    elif mode == "bidir":
        kw.update(q_seg=seg, kv_seg=seg, local_only=True, causal=False)
    out = flash_attention(q, k, v, block_q=32, block_k=32, **kw)
    want = ref.attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(
    Lq=st.integers(8, 80),
    nkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    bq=st.sampled_from([16, 32]),
)
def test_flash_property_random_shapes(Lq, nkv, g, bq):
    """Property: kernel == oracle for arbitrary (Lq, GQA grouping, blocks)."""
    B, dh = 1, 32
    nq = nkv * g
    ks = jax.random.split(jax.random.key(Lq * 131 + nq), 3)
    q = jax.random.normal(ks[0], (B, Lq, nq, dh))
    k = jax.random.normal(ks[1], (B, Lq, nkv, dh))
    v = jax.random.normal(ks[2], (B, Lq, nkv, dh))
    pos = jnp.arange(Lq)
    out = flash_attention(q, k, v, q_pos=pos, kv_pos=pos, block_q=bq, block_k=bq)
    want = ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=5e-5)


def test_flash_batched_row_vectors_decode():
    """Pooled-decode shape: (B, 1) per-row query positions/segments against
    a (B, C) per-row kv-segment pool — mixed frontiers, one inactive row
    fully padded with segment -1 — must match the oracle (which the shared
    core makes natively batched)."""
    B, C, nq, nkv, dh = 3, 64, 4, 2, 32
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, 1, nq, dh))
    k = jax.random.normal(ks[1], (B, C, nkv, dh))
    v = jax.random.normal(ks[2], (B, C, nkv, dh))
    kv_pos = jnp.arange(C)  # shared cache positions
    q_pos = jnp.array([[40], [17], [0]])  # per-row frontiers
    q_seg = jnp.array([[3], [1], [-1]])  # row 2: inactive slot
    kv_seg = jnp.stack([
        jnp.repeat(jnp.arange(4), 16),  # row 0: 4-participant partition
        jnp.where(jnp.arange(C) < 20, 1, -1),  # row 1: short occupant
        jnp.full((C,), -1),  # row 2: freed slot — fully masked
    ])
    for local in (False, True):
        out = flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            local_only=local, block_q=32, block_k=32,
        )
        want = ref.attention_ref(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            local_only=local,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=3e-5
        )
    assert np.all(np.asarray(out[2]) == 0.0)  # fully-masked row: zeros


def test_flash_batched_row_vectors_prefill():
    """Coalesced-admission shape: (B, L) per-row segments with -1 padding
    tails (different real lengths per row) + per-row contribution masks."""
    B, L, nq, nkv, dh = 2, 48, 4, 2, 32
    ks = jax.random.split(jax.random.key(8), 4)
    q = jax.random.normal(ks[0], (B, L, nq, dh))
    k = jax.random.normal(ks[1], (B, L, nkv, dh))
    v = jax.random.normal(ks[2], (B, L, nkv, dh))
    pos = jnp.arange(L)
    seg = jnp.stack([
        jnp.where(jnp.arange(L) < 40, jnp.arange(L) // 10, -1),
        jnp.where(jnp.arange(L) < 24, jnp.arange(L) // 6, -1),
    ])
    contrib = jax.random.bernoulli(ks[3], 0.25, (B, L))
    out = flash_attention(
        q, k, v, q_pos=pos, kv_pos=pos, q_seg=seg, kv_seg=seg,
        contributed=contrib, block_q=32, block_k=32,
    )
    want = ref.attention_ref(
        q, k, v, q_pos=pos, kv_pos=pos, q_seg=seg, kv_seg=seg,
        contributed=contrib,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


def test_pallas_backend_no_longer_falls_back_for_batched_vectors(monkeypatch):
    """ops.attention(backend='pallas') with 2-D pos/seg vectors must run the
    Pallas kernel, not silently fall back to the chunked xla path (the
    pre-refactor behavior this repo's SPMD pooled decode was blocked on)."""
    from repro.kernels import ops

    def boom(*a, **k):
        raise AssertionError("pallas call fell back to the chunked xla path")

    monkeypatch.setattr(ops, "_chunked_attention", boom)
    B, C, nq, nkv, dh = 2, 32, 2, 2, 16
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, 1, nq, dh))
    k = jax.random.normal(ks[1], (B, C, nkv, dh))
    v = jax.random.normal(ks[2], (B, C, nkv, dh))
    q_pos = jnp.array([[20], [9]])
    q_seg = jnp.array([[0], [0]])
    kv_seg = jnp.zeros((B, C), jnp.int32)
    out = ops.attention(
        q, k, v, q_pos=q_pos, kv_pos=jnp.arange(C), q_seg=q_seg,
        kv_seg=kv_seg, backend="pallas",
    )
    want = ref.attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=jnp.arange(C), q_seg=q_seg,
        kv_seg=kv_seg,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,dk,chunk", [(1, 48, 2, 16, 16), (2, 70, 3, 32, 16)])
def test_rwkv6_sweep(B, L, H, dk, chunk, dtype):
    ks = jax.random.split(jax.random.key(0), 5)
    r = jax.random.normal(ks[0], (B, L, H, dk), dtype)
    k = jax.random.normal(ks[1], (B, L, H, dk), dtype)
    v = jax.random.normal(ks[2], (B, L, H, dk), dtype)
    w = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], (B, L, H, dk))), -5.0).astype(dtype)
    u = (jax.random.normal(ks[4], (H, dk)) * 0.5).astype(dtype)
    y, _ = rwkv6_chunked(r, k, v, w, u, chunk=chunk)
    want, _ = ref.rwkv6_ref(r, k, v, w, u)
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err / scale < TOLS[dtype], (err, scale)


def test_rwkv6_reset_fallback_matches_segments():
    """reset_mask (FedAttn-local) == independently scanning each segment."""
    B, L, H, dk = 1, 24, 2, 8
    ks = jax.random.split(jax.random.key(2), 5)
    r, k, v = (jax.random.normal(ks[i], (B, L, H, dk)) for i in range(3))
    w = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], (B, L, H, dk))), -5.0)
    u = jax.random.normal(ks[4], (H, dk)) * 0.5
    resets = jnp.zeros((L,), bool).at[jnp.array([8, 16])].set(True)
    y, _ = ref.rwkv6_ref(r, k, v, w, u, reset_mask=resets)
    pieces = []
    for lo, hi in ((0, 8), (8, 16), (16, 24)):
        yp, _ = ref.rwkv6_ref(
            r[:, lo:hi], k[:, lo:hi], v[:, lo:hi], w[:, lo:hi], u
        )
        pieces.append(yp)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.concatenate(pieces, axis=1)), atol=1e-5
    )


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,d_in,ds", [(1, 40, 32, 8), (2, 70, 48, 16)])
def test_mamba_sweep(B, L, d_in, ds, dtype):
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (B, L, d_in), dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, L, d_in))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (d_in, ds)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, ds), dtype)
    C = jax.random.normal(ks[4], (B, L, ds), dtype)
    D = jnp.ones((d_in,))
    y, _ = mamba_scan_chunked(x, delta, A, Bm, C, D, chunk=16, block_d=32)
    want, _ = ref.mamba_scan_ref(x, delta, A, Bm, C, D)
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err / scale < TOLS[dtype], (err, scale)


def test_mamba_state_continuation():
    """Chunk boundaries are invisible: splitting L in two with state carry
    equals one scan (validates the inter-chunk state plumbing the SPMD
    hand-off relies on)."""
    B, L, d_in, ds = 1, 32, 16, 8
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (B, L, d_in))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, L, d_in)))
    A = -jnp.exp(jax.random.normal(ks[2], (d_in, ds)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, ds))
    C = jax.random.normal(ks[4], (B, L, ds))
    D = jnp.zeros((d_in,))
    y_full, h_full = ref.mamba_scan_ref(x, delta, A, Bm, C, D)
    y1, h1 = ref.mamba_scan_ref(x[:, :16], delta[:, :16], A, Bm[:, :16], C[:, :16], D)
    y2, h2 = ref.mamba_scan_ref(
        x[:, 16:], delta[:, 16:], A, Bm[:, 16:], C[:, 16:], D, initial_state=h1
    )
    np.testing.assert_allclose(np.asarray(y_full[:, 16:]), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), atol=1e-5)


# ---------------------------------------------------------------------------
# chunked (xla) attention — clamp + sentinel conventions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [2048, 64, 100])
def test_chunked_attention_chunk_clamp(chunk):
    """chunk > Lk must not over-pad the KV (the decode default chunk=2048 on
    a short cache used to pad it to a full chunk); all chunk settings match
    the oracle and keep the internal padding below one clamped chunk."""
    from repro.kernels.ops import _chunked_attention

    B, Lq, Lk, nq, nkv, dh = 1, 8, 128, 4, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, Lq, nq, dh))
    k = jax.random.normal(ks[1], (B, Lk, nkv, dh))
    v = jax.random.normal(ks[2], (B, Lk, nkv, dh))
    q_pos = jnp.arange(Lk - Lq, Lk)
    kv_pos = jnp.arange(Lk)
    seg = jnp.repeat(jnp.arange(4), Lk // 4)
    out = _chunked_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=seg[-Lq:], kv_seg=seg,
        causal=True, local_only=True, contributed=None, window=None,
        soft_cap=None, sm_scale=None, chunk=chunk,
    )
    want = ref.attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=seg[-Lq:], kv_seg=seg,
        local_only=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


def test_negative_kv_segments_are_padding_sentinels():
    """kv_seg < 0 marks bucketing padding: those slots must be invisible in
    BOTH phases (sync layers included — position sentinels aside)."""
    B, Lk, nq, nkv, dh = 1, 32, 2, 2, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, 1, nq, dh))
    k = jax.random.normal(ks[1], (B, Lk, nkv, dh))
    v = jax.random.normal(ks[2], (B, Lk, nkv, dh))
    kv_pos = jnp.arange(Lk)
    q_pos = jnp.array([Lk - 1])
    q_seg = jnp.array([0])
    kv_seg_clean = jnp.zeros((Lk,), jnp.int32)
    # same positions, but the last 8 slots marked as padding with garbage KV
    kv_seg_pad = kv_seg_clean.at[24:].set(-1)
    want = ref.attention_ref(
        q, k[:, :24], v[:, :24], q_pos=q_pos, kv_pos=kv_pos[:24],
        q_seg=q_seg, kv_seg=kv_seg_clean[:24],
    )
    got = ref.attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg_pad
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def _mamba_operands(B=1, L=16, d_in=16, ds=8):
    ks = jax.random.split(jax.random.key(2), 6)
    return dict(
        x=jax.random.normal(ks[0], (B, L, d_in)),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (B, L, d_in))),
        A=-jnp.exp(jax.random.normal(ks[2], (d_in, ds)) * 0.3),
        Bm=jax.random.normal(ks[3], (B, L, ds)),
        C=jax.random.normal(ks[4], (B, L, ds)),
        D=jnp.zeros((d_in,)),
    ), jax.random.normal(ks[5], (B, d_in, ds))


def test_pallas_stateful_recurrence_runs_the_reference_scan():
    """ops decides the kernel/reference split: a state-carrying call (the
    decode path) on the pallas backend is exactly the reference scan."""
    from repro.kernels import ops

    a, h0 = _mamba_operands()
    got, h = ops.mamba_scan(**a, initial_state=h0, backend="pallas")
    want, h_want = ref.mamba_scan_ref(**a, initial_state=h0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_want))


def test_pallas_recurrence_raises_where_the_kernel_would_compile(monkeypatch):
    """Where kernels compile (a TPU), the recurrent Pallas kernels would be
    refused by the compiler; ops names that instead of failing inside it."""
    from repro.kernels import core, ops

    monkeypatch.setattr(core, "pallas_interpret", lambda interpret=None: False)
    a, _ = _mamba_operands()
    with pytest.raises(NotImplementedError, match="mamba_scan"):
        ops.mamba_scan(**a, backend="pallas")
