"""Grouped-query attention bodies: parity with the broadcast formulation and
the absence of any K/V copy at the query-head count.

``kernels.core.masked_attention`` and ``kernels.ops._online_attention``
contract query head ``h = kv·g + j`` against kv head ``kv`` at K/V's own
``nkv`` heads. The broadcast body they replace (K/V repeated to ``nq``
heads in float32, then contracted head by head) is kept here as the
oracle; both must agree to a few f32 ulps in every output layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import iter_eqns
from repro.kernels import core, ops

NKV, DH, LK = 2, 16, 24


def broadcast_oracle(q, k, v, mask, *, soft_cap=None, return_stats=False,
                     return_probs=False):
    """The broadcast GQA body: K/V repeated to every query head in f32."""
    B, Lq, nq, dh = q.shape
    g = nq // k.shape[2]
    if mask.ndim == 2:
        mask = mask[None]
    qf = q.astype(jnp.float32) * dh**-0.5
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf, precision="highest")
    if soft_cap:
        s = jnp.tanh(s / soft_cap) * soft_cap
    s = jnp.where(mask[:, None], s, core.NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask[:, None], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, vf, precision="highest")
    if return_stats:
        return (m, l, acc, p) if return_probs else (m, l, acc)
    return acc / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]


def _operands(g, Lq, B=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Lq, NKV * g, DH), jnp.float32)
    k = jax.random.normal(ks[1], (B, LK, NKV, DH), jnp.float32)
    v = jax.random.normal(ks[2], (B, LK, NKV, DH), jnp.float32)
    return q, k, v


def _vectors(kind, Lq, B=3):
    """FedAttn position/segment vectors. ``shared``: 1-D, one (Lq, Lk)
    mask; ``per_row``: 2-D, a (B, Lq, Lk) mask whose last row sees no
    column (every kv segment there is padding)."""
    q_pos = jnp.arange(LK - Lq, LK)
    kv_pos = jnp.arange(LK)
    seg = jnp.repeat(jnp.arange(3), LK // 3)
    if kind == "shared":
        return q_pos, kv_pos, seg[-Lq:], seg
    kv_seg = jnp.stack([seg, jnp.zeros_like(seg), jnp.full_like(seg, core.PAD_SEGMENT)])
    q_seg = jnp.stack([seg[-Lq:], jnp.zeros((Lq,), seg.dtype), seg[-Lq:]])
    return jnp.broadcast_to(q_pos, (B, Lq)), jnp.broadcast_to(kv_pos, (B, LK)), q_seg, kv_seg


def _mask(kind, Lq):
    mask = core.visibility(*_vectors(kind, Lq), local_only=True)
    return mask[0] if kind == "shared" else mask


def _value_sum_tolerance(q, k, v, mask, soft_cap):
    """Per-element bound for a value sum ``acc`` (B, Lq, nq, dh) taken in
    another order: 4 f32 ulps of ``Σ_t |p_t|·|v_t|`` plus 1e-6. The two
    bodies sum the same products, so they may differ by rounding of the
    partial sums and by nothing else."""
    _, _, _, p = broadcast_oracle(q, k, v, mask, soft_cap=soft_cap,
                                  return_stats=True, return_probs=True)
    g = q.shape[2] // k.shape[2]
    va = jnp.repeat(jnp.abs(v), g, axis=2)
    mag = jnp.einsum("bhqk,bkhd->bqhd", jnp.abs(p), va, precision="highest")
    return 1e-6 + 4 * np.finfo(np.float32).eps * np.asarray(mag)


@pytest.mark.parametrize("form", ["out", "stats", "stats_probs"])
@pytest.mark.parametrize("soft_cap", [None, 5.0])
@pytest.mark.parametrize("mask_kind", ["shared", "per_row"])
@pytest.mark.parametrize("Lq", [1, 5])
@pytest.mark.parametrize("g", [1, 2, 7])
def test_grouped_body_matches_broadcast_oracle(g, Lq, mask_kind, soft_cap, form):
    """Every output in its layout: ``m``/``l``/``p`` within rtol = atol =
    1e-6, the value sums within a few ulps of their magnitude."""
    q, k, v = _operands(g, Lq)
    mask = _mask(mask_kind, Lq)
    kw = dict(soft_cap=soft_cap, return_stats=form != "out",
              return_probs=form == "stats_probs")
    got = core.masked_attention(q, k, v, mask, **kw)
    want = broadcast_oracle(q, k, v, mask, **kw)
    tol = _value_sum_tolerance(q, k, v, mask, soft_cap)
    if form == "out":
        _, l, _ = broadcast_oracle(q, k, v, mask, soft_cap=soft_cap, return_stats=True)
        tol = tol / np.maximum(np.asarray(l), 1e-20).transpose(0, 2, 1)[..., None]
        got, want = (got,), (want,)
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a), np.asarray(b)
        if i == 2 or form == "out":  # acc, or out = acc / l
            assert np.all(np.abs(a - b) <= tol), np.max(np.abs(a - b) - tol)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    if mask_kind == "per_row" and form == "out":
        # the last row sees no column: exact zeros, never NaN
        assert np.all(got[0][-1] == 0.0)


@pytest.mark.parametrize("g", [1, 7])
def test_online_body_matches_broadcast_oracle(g):
    """The chunked online-softmax body (several chunks, a padded tail)
    agrees with the one-shot broadcast body to f32 rounding."""
    Lq = 5
    q, k, v = _operands(g, Lq, seed=1)
    q_pos, kv_pos, q_seg, kv_seg = _vectors("per_row", Lq)
    got = ops._chunked_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
        causal=True, local_only=True, contributed=None, window=None,
        soft_cap=5.0, sm_scale=None, chunk=7,
    )
    want = broadcast_oracle(q, k, v, _mask("per_row", Lq), soft_cap=5.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got)[-1] == 0.0)


# ---------------------------------------------------------------------------
# structure: no K/V tensor at nq heads; f32 contractions at HIGHEST
# ---------------------------------------------------------------------------

NQ_S, NKV_S, DH_S, PS, B_S = 28, 4, 8, 4, 3


def _paged_operands():
    N, Pp = 10, 4
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B_S, 1, NQ_S, DH_S), jnp.bfloat16)
    pk = jax.random.normal(ks[1], (N, PS, NKV_S, DH_S), jnp.bfloat16)
    pv = jax.random.normal(ks[2], (N, PS, NKV_S, DH_S), jnp.bfloat16)
    pages = jnp.array([[0, 1, 2, N], [3, 4, N, N], [5, 6, 7, 8]], jnp.int32)
    Lk = Pp * PS
    vec = dict(
        q_pos=jnp.full((B_S, 1), Lk - 1, jnp.int32),
        kv_pos=jnp.broadcast_to(jnp.arange(Lk, dtype=jnp.int32), (B_S, Lk)),
    )
    return q, pk, pv, pages, vec


def _trace(path):
    q, pk, pv, pages, vec = _paged_operands()
    Lk = width = pages.shape[1] * PS  # width: KV columns per contraction
    if path == "paged_decode":
        fn = lambda q, pk, pv: ops.paged_decode_attention(
            q, pk, pv, pages, backend="xla", **vec
        )
    elif path == "chunked_one_chunk":
        k = ops._gather_pages(pk, pages)
        v = ops._gather_pages(pv, pages)
        fn = lambda q, pk, pv: ops._chunked_attention(
            q, k, v, q_seg=None, kv_seg=None, causal=True, local_only=False,
            contributed=None, window=None, soft_cap=None, sm_scale=None,
            chunk=Lk, **vec,
        )
    else:  # the page-group stream beyond densify_elems
        width = 2 * PS
        fn = lambda q, pk, pv: ops._chunked_paged_attention(
            q, pk, pv, pages, q_seg=None, kv_seg=None, causal=True,
            local_only=False, contributed=None, window=None, soft_cap=None,
            sm_scale=None, chunk=width, **vec,
        )
    return jax.make_jaxpr(fn)(q, pk, pv), width


@pytest.mark.parametrize("path", ["paged_decode", "chunked_one_chunk", "chunked_paged"])
def test_no_kv_broadcast_to_query_heads(path):
    """No equation builds K or V at the query-head count, (B, Lk, nq, dh)
    or any view of it, and every f32 contraction runs at HIGHEST."""
    jaxpr, width = _trace(path)
    eqns = list(iter_eqns(jaxpr.jaxpr))
    kv_at_nq = B_S * width * NQ_S * DH_S  # elements of (B, Lk, nq, dh)
    for e in eqns:
        for o in e.outvars:
            assert np.prod(o.aval.shape) < kv_at_nq, (
                f"K/V at {NQ_S} query heads: {e.primitive} -> {o.aval.shape}"
            )
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) >= 2
    for e in dots:
        if all(x.aval.dtype == jnp.float32 for x in e.invars):
            prec = e.params["precision"]
            assert prec is not None and all(
                p == jax.lax.Precision.HIGHEST for p in prec
            ), f"f32 dot_general at {prec}"
