"""Fused Pallas paged flash-decode (kernels/flash_decode.py).

Parity discipline: the interpret-mode kernel is pinned BITWISE against
``paged_flash_decode_ref`` — the pure-XLA twin with the identical
per-page partition — across page sizes (7/8/16: degenerate, pow2,
non-pow2), sentinel/hole page tables, GQA ratios, windows, segments,
sparse-exchange ``contributed`` thinning, verify rows (S = k+1) and
quantized (int8/fp8) pools. The ONE exception is ``soft_cap``, where the
backend's ``tanh`` wobbles at the last ulp with vectorization shape —
those cases assert to f32 rounding (documented in the module docstring).

Above the kernel: ops backend dispatch + ``return_mass`` equivalence,
the ``PagedReadConfig`` knob, full scheduler-trace parity (greedy +
sampled + speculative + quantized) under the zero-recompile budgets,
the 'attnmass' accumulation wiring, and the jaxpr ``pool_gather`` audit
with its teeth (the XLA twin MUST trip it).
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import stack_config
from repro.kernels import flash_decode as FD
from repro.kernels import ops
from repro.serving import FedAttnEngine, Request, quant
from repro.serving.scheduler import ContinuousBatchingScheduler

# degenerate page, the pow2 fast path, pow2+bigger — same boundary
# discipline as tests/test_paging.py
PAGE_SIZES = (7, 8, 16)


def _scenario(seed, *, B=2, S=1, nq=4, g=2, dh=8, ps=8, Pp=3, N=7,
              holes=True, segs=False, kv_quant=None):
    """Random pool + page tables. Tables mix live pages and (with
    ``holes``) sentinel entries (== N); positions are the frontier shape
    the scheduler produces: contiguous per-row kv positions, query rows
    at the causal frontier."""
    nkv = nq // g
    ks = jax.random.split(jax.random.key(seed), 8)
    q = jax.random.normal(ks[0], (B, S, nq, dh), jnp.float32)
    pk = jax.random.normal(ks[1], (N, ps, nkv, dh), jnp.float32)
    pv = jax.random.normal(ks[2], (N, ps, nkv, dh), jnp.float32)
    pages = jax.random.randint(ks[3], (B, Pp), 0, N)
    if holes:
        # every row keeps page 0 live; a random suffix goes sentinel
        n_hole = jax.random.randint(ks[4], (B,), 1, Pp)
        hole = jnp.arange(Pp)[None, :] >= (Pp - n_hole[:, None])
        pages = jnp.where(hole, N, pages)
    Lk = Pp * ps
    kv_pos = jnp.broadcast_to(jnp.arange(Lk, dtype=jnp.int32), (B, Lk))
    # frontier rows: the S queries sit at the last S live positions
    lens = jax.random.randint(ks[5], (B,), S, Lk + 1)
    q_pos = lens[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None, :]
    kw = dict(q_pos=q_pos, kv_pos=kv_pos)
    if segs:
        bnd = int(Lk // 2)
        kv_seg = (jnp.arange(Lk) >= bnd).astype(jnp.int32)
        kw["kv_seg"] = jnp.broadcast_to(kv_seg, (B, Lk))
        kw["q_seg"] = jnp.ones((B, S), jnp.int32)
    if kv_quant is not None:
        sd = quant.storage_dtype(kv_quant)
        pk, sk = quant.quantize_block(pk, sd)
        pv, sv = quant.quantize_block(pv, sd)
        kw["k_scales"], kw["v_scales"] = sk, sv
    return q, pk, pv, pages, kw


def _assert_parity(fused, ref, *, bitwise):
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(ref)):
        a, b = np.asarray(a), np.asarray(b)
        if bitwise:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# kernel vs XLA twin
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    ps=st.sampled_from(PAGE_SIZES),
    g=st.sampled_from((1, 2)),
    S=st.sampled_from((1, 4)),
    window=st.sampled_from((None, 5)),
    soft_cap=st.sampled_from((None, 10.0)),
    holes=st.booleans(),
)
def test_fused_matches_ref_sweep(seed, ps, g, S, window, soft_cap, holes):
    q, pk, pv, pages, kw = _scenario(
        seed, S=S, g=g, ps=ps, holes=holes
    )
    kw.update(window=window, soft_cap=soft_cap)
    fused = FD.paged_flash_decode(q, pk, pv, pages, return_mass=True, **kw)
    ref = FD.paged_flash_decode_ref(q, pk, pv, pages, return_mass=True, **kw)
    # soft_cap: tanh wobbles 1 ulp with vectorization shape (module doc)
    _assert_parity(fused, ref, bitwise=soft_cap is None)


@pytest.mark.parametrize("kv_quant", ["int8", "fp8"])
@pytest.mark.parametrize("S", [1, 4])
def test_fused_quantized_bitwise(kv_quant, S):
    """In-kernel dequant (codes × per-page-per-head scale at load) is
    bitwise against the twin's gather-then-dequantize of the same blocks."""
    q, pk, pv, pages, kw = _scenario(3, S=S, ps=8, kv_quant=kv_quant)
    assert pk.dtype == quant.storage_dtype(kv_quant)
    fused = FD.paged_flash_decode(q, pk, pv, pages, return_mass=True, **kw)
    ref = FD.paged_flash_decode_ref(q, pk, pv, pages, return_mass=True, **kw)
    _assert_parity(fused, ref, bitwise=True)


def test_fused_segments_contributed_publisher():
    """The full visibility vocabulary in-kernel: cross-participant masking,
    local_only, sparse-exchange ``contributed`` thinning, and the
    causal=False + publisher_lo prefill-style form — all bitwise."""
    q, pk, pv, pages, kw = _scenario(7, S=2, ps=8, segs=True)
    Lk = pages.shape[1] * pk.shape[1]
    ct = (jnp.arange(Lk) % 3 == 0)[None, :].repeat(q.shape[0], axis=0)
    variants = [
        dict(kw),
        dict(kw, local_only=True),
        dict(kw, contributed=ct),
        dict(kw, causal=False, publisher_lo=4),
    ]
    for v in variants:
        fused = FD.paged_flash_decode(q, pk, pv, pages, **v)
        ref = FD.paged_flash_decode_ref(q, pk, pv, pages, **v)
        _assert_parity(fused, ref, bitwise=True)
    # contributed genuinely thins: output differs from the full-exchange one
    full = FD.paged_flash_decode(q, pk, pv, pages, **variants[0])
    thin = FD.paged_flash_decode(q, pk, pv, pages, **variants[2])
    assert not np.array_equal(np.asarray(full), np.asarray(thin))


def test_fully_masked_rows_are_zero():
    """All-sentinel tables → every column hidden → the core contract says
    exact zero output and zero mass, never NaN."""
    q, pk, pv, pages, kw = _scenario(11, holes=False)
    pages = jnp.full_like(pages, pk.shape[0])
    out, mass = FD.paged_flash_decode(
        q, pk, pv, pages, return_mass=True, **kw
    )
    assert np.array_equal(np.asarray(out), np.zeros_like(out))
    assert np.array_equal(np.asarray(mass), np.zeros_like(mass))


def test_stats_form_recombines_to_output():
    """return_stats emits combinable (m, l, acc) in the masked_attention
    stats vocabulary: normalizing them reproduces the direct output
    bitwise (what the SPMD pmax/psum combine relies on)."""
    q, pk, pv, pages, kw = _scenario(13, S=2)
    out = FD.paged_flash_decode(q, pk, pv, pages, **kw)
    m, l, acc = FD.paged_flash_decode(q, pk, pv, pages, return_stats=True, **kw)
    denom = jnp.maximum(l, 1e-20)  # (B, nq, S)
    re = (acc / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(re))


def test_mass_row_conservation():
    """Each (head, row) distributes exactly one unit of probability mass
    over the pool columns — sum(mass) per slot == nq * S."""
    q, pk, pv, pages, kw = _scenario(17, S=3, holes=True)
    _, mass = FD.paged_flash_decode(q, pk, pv, pages, return_mass=True, **kw)
    B, S, nq = q.shape[0], q.shape[1], q.shape[2]
    np.testing.assert_allclose(
        np.asarray(jnp.sum(mass, axis=1)), np.full((B,), nq * S, np.float32),
        rtol=1e-5,
    )
    # sentinel columns carry zero mass
    col_valid = np.repeat(np.asarray(pages) < pk.shape[0], pk.shape[1], axis=1)
    assert np.all(np.asarray(mass)[~col_valid] == 0.0)


def test_fused_jit_traced_pages_bitwise():
    """Page tables are traced DATA through the scalar-prefetch path: the
    jitted kernel (tables as arguments) matches the eager call bitwise."""
    q, pk, pv, pages, kw = _scenario(19, S=1)
    eager = FD.paged_flash_decode(q, pk, pv, pages, **kw)
    jitted = jax.jit(
        lambda pg, qp: FD.paged_flash_decode(
            q, pk, pv, pg, q_pos=qp, kv_pos=kw["kv_pos"]
        )
    )(pages, kw["q_pos"])
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


# ---------------------------------------------------------------------------
# ops dispatch + the PagedReadConfig knob
# ---------------------------------------------------------------------------


def test_ops_backend_dispatch_agrees():
    """ops.paged_decode_attention(backend='pallas') routes to the fused
    kernel and agrees with the gather path to f32 rounding; return_mass
    agrees across backends the same way."""
    q, pk, pv, pages, kw = _scenario(23, S=1, ps=8)
    x = ops.paged_decode_attention(q, pk, pv, pages, **kw)
    p = ops.paged_decode_attention(q, pk, pv, pages, backend="pallas", **kw)
    np.testing.assert_allclose(np.asarray(x), np.asarray(p), atol=1e-5)
    xm = ops.paged_decode_attention(q, pk, pv, pages, return_mass=True, **kw)
    pm = ops.paged_decode_attention(
        q, pk, pv, pages, backend="pallas", return_mass=True, **kw
    )
    for a, b in zip(xm, pm):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_paged_read_config_knob(monkeypatch):
    """PagedReadConfig is THE read-path knob: forcing the chunk stream
    (densify_elems=0) with a chunk wider than the pool exercises the
    clamp-to-extent rule and must not change the output."""
    q, pk, pv, pages, kw = _scenario(29, S=1, ps=8)
    base = ops.paged_decode_attention(q, pk, pv, pages, **kw)
    monkeypatch.setattr(
        ops, "PAGED_READ",
        ops.PagedReadConfig(densify_elems=0, chunk_tokens=10_000),
    )
    forced = ops.paged_decode_attention(q, pk, pv, pages, **kw)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(forced), atol=1e-6
    )
    assert ops.PagedReadConfig().densify_elems == 256 * 256


# ---------------------------------------------------------------------------
# scheduler traces: the fused backend serves the pool
# ---------------------------------------------------------------------------


def _params(cfg):
    from repro.models import build_model

    return build_model(cfg).init(jax.random.key(0))


def _req(i, L, n_new, temp=0.0):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, 97)
    rng = jax.random.key(100 + i) if temp > 0 else None
    return Request(tokens=toks, n_new=n_new, temperature=temp, rng=rng)


def _run_backends(cfg, params, reqs, *, spec_k=0, kv_quant=None,
                  budgets=None):
    outs, scheds = {}, {}
    for backend in (None, "pallas"):
        eng = FedAttnEngine(cfg, params, backend=backend, kv_quant=kv_quant)
        s = ContinuousBatchingScheduler(
            eng, max_slots=2, capacity=40, kv_layout="paged", page_size=8,
            spec_k=spec_k,
        )
        outs[backend] = s.run(reqs)
        scheds[backend] = s
    return outs, scheds


def test_scheduler_churn_parity_and_budgets(trace_budget):
    """Acceptance: greedy tokens EXACT and logprobs within the documented
    f32-rounding tolerance on a churning paged trace (greedy + sampled,
    retire/admit mid-flight), with the zero-recompile budget holding —
    ONE decode executable for the whole fused trace."""
    cfg = stack_config("attn")
    params = _params(cfg)
    reqs = [_req(0, 14, 8), _req(1, 9, 6, temp=0.7), _req(2, 17, 5)]
    with trace_budget():
        outs, scheds = _run_backends(cfg, params, reqs)
    for a, b in zip(outs[None], outs["pallas"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=2e-5)
    assert scheds["pallas"].compile_counts["decode_step"] == 1


def test_scheduler_speculative_parity():
    """S = k+1 verify rows ride the same fused kernel: speculative traces
    agree token-for-token with the gather backend, ONE verify executable."""
    cfg = stack_config("attn")
    params = _params(cfg)
    reqs = [_req(0, 14, 8), _req(1, 9, 6, temp=0.7), _req(2, 17, 5)]
    outs, scheds = _run_backends(cfg, params, reqs, spec_k=3)
    for a, b in zip(outs[None], outs["pallas"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert scheds["pallas"].compile_counts["verify_step"] == 1


@pytest.mark.parametrize("kv_quant", ["int8", "fp8"])
def test_scheduler_quantized_parity(kv_quant):
    """Quantized pools decode through the in-kernel dequant path: token
    parity with the gather backend at the documented logprob tolerance."""
    cfg = stack_config("attn")
    params = _params(cfg)
    reqs = [_req(0, 14, 6), _req(1, 9, 5, temp=0.7)]
    outs, _ = _run_backends(cfg, params, reqs, kv_quant=kv_quant)
    for a, b in zip(outs[None], outs["pallas"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=2e-5)


# ---------------------------------------------------------------------------
# 'attnmass': accumulated decode mass drives the sparse exchange
# ---------------------------------------------------------------------------


def _attnmass_cfg():
    from repro.types import FedAttnConfig, LayerSpec

    return stack_config(
        "attn",
        pattern=(LayerSpec(), LayerSpec(sync=True)),
        fedattn=FedAttnConfig(
            n_participants=2, sync_interval=2, kv_selection="attnmass",
            kv_exchange_ratio=0.5,
        ),
    )


def test_attnmass_accumulates_and_matches_across_backends():
    """kv_selection='attnmass' on a paged pool: the per-slot mass
    accumulator rides the cache pytree (one 'am' leaf per attn layer),
    accumulates real softmax mass, and both backends agree on tokens."""
    cfg = _attnmass_cfg()
    params = _params(cfg)
    reqs = [_req(0, 12, 6), _req(1, 9, 5)]
    outs = {}
    for backend in (None, "pallas"):
        eng = FedAttnEngine(cfg, params, backend=backend)
        s = ContinuousBatchingScheduler(
            eng, max_slots=2, capacity=32, kv_layout="paged", page_size=8
        )
        assert s._mass_width == s._cap
        outs[backend] = s.run(reqs)
        am = [v for k, v in jax.tree_util.tree_flatten_with_path(s.cache)[0]
              if any(getattr(p, "key", None) == "am" for p in k)]
        # one 'am' leaf per attn layer of the traced plan (scan mode
        # stacks the layer axis INTO the leaf, so count >= 1 either way)
        assert am
        total = sum(float(jnp.sum(a)) for a in am)
        assert total > 0.0  # real mass accumulated, not a dead buffer
        assert s.compile_counts["decode_step"] == 1
    for a, b in zip(outs[None], outs["pallas"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_attnmass_changes_exchange_and_dense_is_unaffected():
    """ratio < 1 with 'attnmass' genuinely thins the sync-layer exchange
    (logprobs differ from the full-exchange run) and the dense layout —
    which has no accumulator — still serves (recency fallback)."""
    cfg = _attnmass_cfg()
    params = _params(cfg)
    reqs = [_req(0, 12, 6)]
    sparse = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params), max_slots=1, capacity=32,
        kv_layout="paged", page_size=8,
    ).run(reqs)
    full_cfg = cfg.replace(fedattn=cfg.fedattn.replace(kv_exchange_ratio=1.0))
    full = ContinuousBatchingScheduler(
        FedAttnEngine(full_cfg, _params(full_cfg)), max_slots=1, capacity=32,
        kv_layout="paged", page_size=8,
    ).run(reqs)
    assert not np.allclose(sparse[0].logprobs, full[0].logprobs)
    dense = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params), max_slots=1, capacity=32,
        kv_layout="dense",
    ).run(reqs)
    assert dense[0].tokens.shape == sparse[0].tokens.shape


def test_contribution_mask_attnmass():
    """core.aggregation grows the 'attnmass' strategy: rank-by-mass within
    each participant when stats exist, recency fallback when they don't
    (prefill admission has no decode stats yet)."""
    from repro.core.aggregation import contribution_mask
    from repro.core.partition import Partition

    part = Partition.contiguous(8, 2)  # 4 + 4 tokens
    mass = jnp.asarray([0.1, 5.0, 0.2, 0.3, 9.0, 0.0, 0.1, 2.0])
    got = contribution_mask(part, 0.5, "attnmass", attn_mass=mass)
    # top-2 per participant by mass: positions 1,3 and 4,7
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray([False, True, False, True, True, False, False, True]),
    )
    fallback = contribution_mask(part, 0.5, "attnmass")
    recency = contribution_mask(part, 0.5, "recency")
    np.testing.assert_array_equal(np.asarray(fallback), np.asarray(recency))


# ---------------------------------------------------------------------------
# static audit: the fused step never densifies the pool
# ---------------------------------------------------------------------------


def test_jaxpr_audit_fused_decode_clean_and_has_teeth():
    from repro.analysis import jaxpr_audit as JA

    cfg = stack_config("attn")
    params = _params(cfg)
    eng = FedAttnEngine(cfg, params, backend="pallas")
    assert JA.audit_fused_decode(eng, spec_k=2) == []

    # teeth: the XLA gather twin MUST trip the pool_gather ban
    sched = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params), max_slots=2, capacity=32,
        kv_layout="paged", page_size=8,
    )
    entries = JA.trace_scheduler_entries(sched)
    step = next(e for e in entries if e.name == "scheduler.decode_step")
    hits = JA.pool_gather_issues(step.name, step.traced, min_pool_rank=5)
    assert hits and all(i.check == "pool_gather" for i in hits)

    # a non-pallas engine is rejected, not silently waved through
    issues = JA.audit_fused_decode(FedAttnEngine(cfg, params))
    assert issues and issues[0].check == "pool_gather"


# ---------------------------------------------------------------------------
# SPMD: shard-local fused kernel + the existing collective combine
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.models import build_model
from repro.serving import FedAttnEngine, Request
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig, LayerSpec, ModelConfig

cfg = ModelConfig(
    name="tiny", arch_type="dense", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=97, dtype="float32",
    pattern=tuple(LayerSpec(sync=(i == 3)) for i in range(4)),
    fedattn=FedAttnConfig(n_participants=2, sync_interval=4),
)
params = build_model(cfg).init(jax.random.key(0))

def req(i, L, n_new, temp=0.0):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, 97)
    rng = jax.random.key(100 + i) if temp > 0 else None
    return Request(tokens=toks, n_new=n_new, temperature=temp, rng=rng)

reqs = [req(0, 14, 6), req(1, 9, 5, temp=0.7), req(2, 17, 4)]
base = FedAttnEngine(cfg, params).generate_many(
    reqs, max_slots=2, capacity=64, kv_layout="paged", page_size=8)

mesh = jax.make_mesh((2,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
eng = FedAttnEngine(cfg, params, mesh=mesh, backend="pallas")
sched = ContinuousBatchingScheduler(
    eng, max_slots=2, capacity=64, kv_layout="paged", page_size=8)
got = sched.run(reqs)

tok_eq = all(np.array_equal(a.tokens, b.tokens) for a, b in zip(base, got))
lp_err = max(
    float(np.abs(a.logprobs - b.logprobs).max()) for a, b in zip(base, got))
print(json.dumps({
    "tokens_equal": bool(tok_eq), "logprob_err": lp_err,
    "decode_execs": sched.compile_counts["decode_step"],
    "n_devices": len(jax.devices()),
}))
"""


def _run(script: str) -> dict:
    import json

    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_spmd_fused_pooled_decode_matches_single_device():
    """2-device mesh, fused backend: each shard runs the flash-decode
    kernel on its pool half and the existing pmax/psum stats combine
    produces single-device tokens exactly."""
    res = _run(_MESH_SCRIPT)
    assert res["n_devices"] == 2, res
    assert res["tokens_equal"], res
    assert res["logprob_err"] < 2e-4, res
    assert res["decode_execs"] == 1, res
