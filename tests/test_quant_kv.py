"""Quantized KV: codec properties (serving/quant.py), pool parity and the
zero-recompile churn contract under int8/fp8 pages, the compressed
sync-layer exchange, the attnmass/seeded-random selection policies, and
the analyzer/validation guard-rails.

Codec properties run under hypothesis (or the vendored deterministic stub
— conftest installs it before collection). The pool tests mirror
test_paged_serving.py: same churning traces, same engines, the paged pool
merely switches storage dtype — parity is the acceptance claim
(dequant-at-gather keeps every consumer on the dense contract)."""
import json
import os
import pathlib
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import stack_config
from repro.serving import FedAttnEngine, Request, quant
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig

# greedy logprobs under per-page scales: the documented tolerances (tokens
# are pinned EXACTLY on the trace; logits move ~1e-3 under int8's 8-bit
# grid, up to ~5e-3 under fp8 e4m3's 3 mantissa bits)
LOGPROB_ATOL = {"int8": 2e-3, "fp8": 1e-2}

# pow2 and its neighbors — catches any &-mask shortcut in page arithmetic
PAGE_SIZES = (7, 8, 9)


def _engine(cfg, **kw):
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.key(0))
    return FedAttnEngine(cfg, params, **kw)


def _req(i, L, n_new, vocab=97):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, vocab)
    return Request(tokens=toks, n_new=n_new)


@pytest.fixture(scope="module")
def attn_eng():
    return _engine(stack_config("attn"))


# ---------------------------------------------------------------------------
# codec properties
# ---------------------------------------------------------------------------


def _page(seed, ps, magnitude=1.0):
    return magnitude * jax.random.normal(
        jax.random.key(seed), (ps, 2, 16), jnp.float32
    )


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 1_000),
    ps=st.sampled_from(PAGE_SIZES),
    mag=st.sampled_from([1e-3, 1.0, 1e3]),
)
def test_int8_block_roundtrip_error_bound(seed, ps, mag):
    """Elementwise |x - deq(q(x))| <= scale/2: the int8 grid step under the
    per-(page, kv-head) scale, the bound the README table documents."""
    x = _page(seed, ps, mag)
    codes, scales = quant.quantize_block(x, jnp.int8)
    assert codes.dtype == jnp.int8 and scales.shape == (2,)
    err = jnp.abs(quant.dequantize(codes, scales[None, :]) - x)
    bound = scales[None, :, None] / 2 * (1 + 1e-6)
    assert bool(jnp.all(err <= bound)), float((err - bound).max())


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 1_000),
    ps=st.sampled_from(PAGE_SIZES),
    mag=st.sampled_from([1e-3, 1.0, 1e3]),
)
def test_fp8_block_roundtrip_error_bound(seed, ps, mag):
    """fp8 e4m3 keeps ~3 mantissa bits: relative error <= 2^-4 of the
    element for normals, absolute <= scale * 2^-10 in the subnormal tail.
    Also pins the clip-before-cast rule — no nan/inf ever comes back."""
    x = _page(seed, ps, mag)
    codes, scales = quant.quantize_block(x, jnp.float8_e4m3fn)
    deq = quant.dequantize(codes, scales[None, :])
    assert bool(jnp.all(jnp.isfinite(deq)))
    err = jnp.abs(deq - x)
    bound = jnp.maximum(
        jnp.abs(x) * 2.0**-4, scales[None, :, None] * 2.0**-10
    ) * (1 + 1e-6)
    assert bool(jnp.all(err <= bound)), float((err - bound).max())


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_all_zero_page_roundtrips_to_exact_zero(dtype):
    """amax 0 → scale 0: encode divides by the f32 tiny guard (no nan/inf)
    and the round-trip is EXACTLY zero — zero-initialized pool pages and
    zero-padded rows stay bit-clean."""
    x = jnp.zeros((8, 2, 16), jnp.float32)
    codes, scales = quant.quantize_block(x, dtype)
    assert bool(jnp.all(scales == 0.0))
    np.testing.assert_array_equal(
        np.asarray(quant.dequantize(codes, scales[None, :])), 0.0
    )


def test_single_outlier_sets_scale_rest_within_bound():
    """One huge element per head owns the amax; the outlier itself and
    every crushed small element still satisfy the scale/2 bound (small
    values may round to 0 — that IS within half a grid step)."""
    x = _page(3, 8, 1e-2)
    x = x.at[4, 0, 7].set(1000.0).at[2, 1, 3].set(-500.0)
    codes, scales = quant.quantize_block(x, jnp.int8)
    np.testing.assert_allclose(
        np.asarray(scales), [1000.0 / 127, 500.0 / 127], rtol=1e-6
    )
    err = jnp.abs(quant.dequantize(codes, scales[None, :]) - x)
    assert bool(jnp.all(err <= scales[None, :, None] / 2 * (1 + 1e-6)))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1_000), ps=st.sampled_from(PAGE_SIZES))
def test_zero_padded_rows_stay_zero_and_real_rows_bounded(seed, ps):
    """A partially filled page (real rows + zero padding, the pool's state
    between admission and the frontier): padding round-trips to exact zero
    and the real rows keep the scale/2 bound — the page-wide amax is set
    by real data only, so masked-out rows never poison visibility math."""
    n_real = ps // 2 + 1
    x = _page(seed, ps).at[n_real:].set(0.0)
    codes, scales = quant.quantize_block(x, jnp.int8)
    deq = quant.dequantize(codes, scales[None, :])
    np.testing.assert_array_equal(np.asarray(deq[n_real:]), 0.0)
    err = jnp.abs(deq[:n_real] - x[:n_real])
    assert bool(jnp.all(err <= scales[None, :, None] / 2 * (1 + 1e-6)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1_000), mag=st.sampled_from([1e-3, 1.0, 1e3]))
def test_quantize_rows_exchange_codec_bound(seed, mag):
    """The per-row-per-head EXCHANGE codec: scales shape (..., nkv) and
    the same scale/2 elementwise bound — what sync-layer KV rows tolerate
    on the wire."""
    x = mag * jax.random.normal(jax.random.key(seed), (3, 5, 2, 16))
    codes, scales = quant.quantize_rows(x, jnp.int8)
    assert scales.shape == (3, 5, 2)
    err = jnp.abs(quant.dequantize(codes, scales) - x)
    assert bool(jnp.all(err <= scales[..., None] / 2 * (1 + 1e-6)))


# ---------------------------------------------------------------------------
# paged_write: scatter-max scales, untouched pages bit-exact, sentinel drop
# ---------------------------------------------------------------------------


def test_paged_write_untouched_page_bit_exact_and_scale_growth():
    """Frontier write into page 0 with a LARGER amax: page 0's scale grows
    and its resident codes rescale once; page 1 (untouched) keeps codes
    AND scale bit-identical — the ratio-1 re-encode is exactly the
    identity, so resident codes never drift across decode steps."""
    blocks = jnp.stack([_page(0, 8), _page(1, 8)])  # (2, ps, nkv, dh)
    pool, scales = quant.quantize_block(blocks, jnp.int8)
    new = 50.0 * jnp.ones((1, 1, 2, 16), jnp.float32)  # amax >> page 0's
    page_idx = jnp.array([[0]], jnp.int32)
    off = jnp.array([[3]], jnp.int32)
    pool2, scales2 = quant.paged_write(pool, scales, new, page_idx, off)
    np.testing.assert_array_equal(np.asarray(pool2[1]), np.asarray(pool[1]))
    np.testing.assert_array_equal(
        np.asarray(scales2[1]), np.asarray(scales[1])
    )
    assert bool(jnp.all(scales2[0] > scales[0]))
    np.testing.assert_allclose(np.asarray(scales2[0]), 50.0 / 127, rtol=1e-6)
    # the written row round-trips under the grown scale
    deq = quant.dequantize(pool2[0, 3], scales2[0])
    np.testing.assert_allclose(np.asarray(deq), 50.0, rtol=0.5 / 127)
    # resident rows of page 0 survive the one-time rescale within the
    # GROWN grid step (coarser than the original — that's the trade)
    old = quant.dequantize(pool[0, 0], scales[0])
    resc = quant.dequantize(pool2[0, 0], scales2[0])
    assert bool(jnp.all(jnp.abs(resc - old) <= scales2[0][:, None]))


def test_paged_write_sentinel_drops_bitwise():
    """page_idx >= num_pages is the paging sentinel: the write must drop —
    pool and scales come back bit-identical (retired slots scribble
    nowhere, matching the unquantized ``mode='drop'`` scatter)."""
    pool, scales = quant.quantize_block(
        jnp.stack([_page(0, 8), _page(1, 8)]), jnp.int8
    )
    pool2, scales2 = quant.paged_write(
        pool, scales, 99.0 * jnp.ones((1, 1, 2, 16)),
        jnp.array([[2]], jnp.int32), jnp.array([[0]], jnp.int32),
    )
    np.testing.assert_array_equal(np.asarray(pool2), np.asarray(pool))
    np.testing.assert_array_equal(np.asarray(scales2), np.asarray(scales))


# ---------------------------------------------------------------------------
# pool parity + the zero-recompile churn contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quant_pool_matches_dense_greedy_tokens(attn_eng, mode):
    """Acceptance: greedy tokens EXACT vs the dense pool on a churning
    mixed-length trace; logprobs within the documented ~2e-3 tolerance
    (per-page scales keep logit error well under greedy decision
    margins). Dequant-at-gather means the quantized pool exercises the
    same attention consumers as the f32 one."""
    reqs = [
        _req(0, 24, 8), _req(1, 17, 5), _req(2, 30, 3),
        _req(3, 9, 12), _req(4, 11, 2),
    ]
    dense = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="dense"
    ).run(reqs)
    paged = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="paged",
        page_size=16, kv_quant=mode,
    ).run(reqs)
    for i, (a, b) in enumerate(zip(dense, paged)):
        np.testing.assert_array_equal(a.tokens, b.tokens, err_msg=f"req {i}")
        np.testing.assert_allclose(
            a.logprobs, b.logprobs, atol=LOGPROB_ATOL[mode],
            err_msg=f"req {i}",
        )


def test_quant_churn_zero_new_executables(attn_eng):
    """Scales are DATA: the churning trace ends with ONE decode executable,
    and replaying a fresh same-bucket trace through the warm pool adds
    ZERO executables of any kind — quantized admission/retirement churn
    never recompiles (the PR's zero-recompile pin)."""
    reqs = [_req(i, 10 + 3 * i, 2 + i) for i in range(6)]
    sched = ContinuousBatchingScheduler(
        attn_eng, max_slots=3, capacity=64, kv_layout="paged",
        page_size=16, kv_quant="int8",
    )
    sched.run(reqs)
    cc = sched.compile_counts
    assert cc["decode_step"] == 1, cc
    assert cc["slot_write"] == 1, cc
    n_prefill = cc["prefill"]
    sched.run([_req(20 + i, 11 + 5 * i, 3 + i) for i in range(4)])
    cc2 = sched.compile_counts
    assert cc2["decode_step"] == 1 and cc2["prefill"] == n_prefill, cc2


def test_quant_pool_prefix_cache_parity(attn_eng):
    """Prefix-cached shared-prompt pages work quantized: the second batch
    maps the first batch's prompt pages copy-free and still matches the
    dense pool's greedy tokens — shared pages are shared CODES + shared
    scales, both refcounted as one unit."""
    sys_prompt = np.asarray(jax.random.randint(
        jax.random.key(1), (32,), 0, 97))
    reqs = []
    for i in range(4):
        tail = np.asarray(jax.random.randint(
            jax.random.key(50 + i), (3 + i,), 0, 97))
        reqs.append(Request(
            tokens=np.concatenate([sys_prompt, tail]).astype(np.int32),
            n_new=4,
        ))
    dense = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="dense"
    ).run(reqs)
    sched = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="paged", page_size=8,
        kv_quant="int8", prefix_cache=True,
    )
    paged = sched.run(reqs)
    assert sched.pool_stats()["prefix_hits"] > 0
    for i, (a, b) in enumerate(zip(dense, paged)):
        np.testing.assert_array_equal(a.tokens, b.tokens, err_msg=f"req {i}")


def test_kv_quant_requires_paged_layout(attn_eng):
    """Dense slot rows have no per-page scale leaves to attach — asking
    for kv_quant on the dense layout is a config error, not a silent
    no-op."""
    with pytest.raises(ValueError, match="kv_layout='paged'"):
        ContinuousBatchingScheduler(
            attn_eng, max_slots=2, capacity=64, kv_layout="dense",
            kv_quant="int8",
        )


@pytest.mark.parametrize("kind", ["hybrid", "rwkv"])
def test_recurrent_stacks_raise_not_implemented(kind):
    """Recurrent layers carry per-slot STATE, not per-position KV — no
    page/row granularity to attach scales to. The blocker is named, not
    silently ignored."""
    from repro.configs import schedule_from_config
    from repro.models import transformer as T

    cfg = stack_config(kind)
    plan = T.ScanPlan.from_schedule(cfg, schedule_from_config(cfg))
    with pytest.raises(NotImplementedError, match="attention-only stack"):
        T.init_paged_cache(cfg, 2, 8, 8, plan=plan, kv_quant="int8")


def test_kv_quant_config_validation():
    with pytest.raises(ValueError, match="kv_quant"):
        FedAttnConfig(n_participants=2, kv_quant="int4")


# ---------------------------------------------------------------------------
# sync-layer exchange: compressed bytes + roundtrip
# ---------------------------------------------------------------------------


def test_exchange_bytes_per_row_ratio():
    """The wire codec (dh int8 codes + nkv f32 scales per row) vs plain
    f32 rows: 2*nkv*dh*4 over 2*nkv*(dh+4) = 3.56x at dh=32 — the >=3.5x
    shrink the PR pins. Unknown modes are config errors."""
    from repro.core.aggregation import exchange_bytes_per_row

    plain = exchange_bytes_per_row(2, 32, "none", bytes_per_el=4)
    q8 = exchange_bytes_per_row(2, 32, "int8", bytes_per_el=4)
    assert plain == 2 * 2 * 32 * 4
    assert q8 == 2 * 2 * (32 + 4)
    assert plain / q8 >= 3.5
    # fp8 rides the same row layout: dh 1-byte codes + nkv f32 scales
    assert exchange_bytes_per_row(2, 32, "fp8", bytes_per_el=4) == q8
    with pytest.raises(ValueError):
        exchange_bytes_per_row(2, 32, "int4")


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_exchange_roundtrip_bound(mode):
    """What sync-layer KV loses crossing the wire: the per-row codec's
    documented bound, and 'none' is the exact identity."""
    from repro.core.aggregation import quantized_exchange_roundtrip

    k = jax.random.normal(jax.random.key(0), (2, 12, 2, 16))
    v = jax.random.normal(jax.random.key(1), (2, 12, 2, 16))
    k2, v2 = quantized_exchange_roundtrip(k, v, mode)
    amax = jnp.max(jnp.abs(k), axis=-1, keepdims=True)
    bound = amax / 127 / 2 if mode == "int8" else amax * 2.0**-4
    assert bool(jnp.all(jnp.abs(k2 - k) <= bound * (1 + 1e-6)))
    assert bool(jnp.all(jnp.isfinite(v2)))
    k3, v3 = quantized_exchange_roundtrip(k, v, "none")
    assert k3 is k and v3 is v


# ---------------------------------------------------------------------------
# selection policies: attnmass vs keynorm, seeded random
# ---------------------------------------------------------------------------


def test_attnmass_disagrees_with_keynorm_where_it_should():
    """The constructed disagreement: rows 0/2 have the largest key norms
    but received (almost) no attention mass; rows 1/3 are small-norm rows
    the queries actually used. keynorm keeps the loud rows, attnmass the
    used ones — the exact failure mode of the static-norm proxy."""
    from repro.distributed.spmd_attention import _select_rows

    keys = jnp.zeros((1, 4, 2, 8), jnp.float32)
    for row, norm in enumerate((10.0, 1.0, 5.0, 0.1)):
        keys = keys.at[0, row, :, 0].set(norm)
    mass = jnp.array([0.0, 0.9, 0.05, 0.8], jnp.float32)
    pos = jnp.arange(4)
    by_norm = _select_rows(pos, 4, 2, "keynorm", keys=keys)
    by_mass = _select_rows(pos, 4, 2, "attnmass", attn_mass=mass)
    np.testing.assert_array_equal(np.asarray(by_norm), [0, 2])
    np.testing.assert_array_equal(np.asarray(by_mass), [1, 3])
    with pytest.raises(ValueError, match="attnmass"):
        _select_rows(pos, 4, 2, "attnmass")


def test_random_selection_seeded_and_per_round():
    """'random' with an rng key is real sampling: deterministic per
    (key, round) via fold_in, different across rounds, always the static
    n_keep count. Without a key the deprecated strided alias survives —
    with a warning."""
    from repro.distributed.spmd_attention import _select_rows

    pos, Ls, n_keep = jnp.arange(64), 64, 8
    key = jax.random.key(7)
    a = _select_rows(pos, Ls, n_keep, "random", rng=key, round_index=0)
    b = _select_rows(pos, Ls, n_keep, "random", rng=key, round_index=0)
    c = _select_rows(pos, Ls, n_keep, "random", rng=key, round_index=1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.shape == (n_keep,)
    assert bool(jnp.all(a[1:] > a[:-1]))  # sorted, duplicate-free gather
    with pytest.warns(UserWarning, match="deprecated"):
        legacy = _select_rows(pos, Ls, n_keep, "random")
    np.testing.assert_array_equal(
        np.asarray(legacy),
        np.asarray(_select_rows(pos, Ls, n_keep, "strided")),
    )


# ---------------------------------------------------------------------------
# analyzer guard-rail
# ---------------------------------------------------------------------------


@pytest.mark.analysis
def test_audit_quant_pool_clean_and_detects_unquantized(attn_eng):
    """The jaxpr audit proves the pool buffers are ACTUALLY int8 in the
    compiled decode/slot-write entry points (not silently upcast f32
    pools wearing a quant label), and reports when no mode is set."""
    from repro.analysis.jaxpr_audit import audit_quant_pool

    sched = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="paged",
        page_size=16, kv_quant="int8",
    )
    sched.run([_req(0, 12, 3), _req(1, 20, 4)])
    assert audit_quant_pool(sched) == []
    plain = ContinuousBatchingScheduler(
        attn_eng, max_slots=2, capacity=64, kv_layout="paged", page_size=16,
    )
    issues = audit_quant_pool(plain)
    assert len(issues) == 1 and issues[0].check == "storage"


# ---------------------------------------------------------------------------
# multi-device mesh parity (slow subprocess, 2 fake CPU devices)
# ---------------------------------------------------------------------------

_QUANT_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from repro.serving import FedAttnEngine, Request
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig, LayerSpec, ModelConfig

cfg = ModelConfig(
    name="tiny", arch_type="dense", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=97, dtype="float32",
    pattern=tuple(LayerSpec(sync=(i == 3)) for i in range(4)),
    fedattn=FedAttnConfig(n_participants=4, sync_interval=4),
)
from repro.models import build_model
params = build_model(cfg).init(jax.random.key(0))

def req(i, L, n_new):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, cfg.vocab_size)
    return Request(tokens=toks, n_new=n_new)

reqs = [req(0, 24, 6), req(1, 17, 4), req(2, 30, 3), req(3, 9, 8)]

single = FedAttnEngine(cfg, params)
base = ContinuousBatchingScheduler(
    single, max_slots=2, capacity=64, kv_layout="paged", page_size=16,
    kv_quant="int8",
).run(reqs)

mesh = jax.make_mesh((2,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
eng = FedAttnEngine(cfg, params, mesh=mesh)
sched = ContinuousBatchingScheduler(
    eng, max_slots=2, capacity=64, kv_layout="paged", page_size=16,
    kv_quant="int8",
)
got = sched.run(reqs)

tok_eq = all(np.array_equal(a.tokens, b.tokens) for a, b in zip(base, got))
lp_err = max(
    float(np.abs(a.logprobs - b.logprobs).max()) for a, b in zip(base, got)
)
print(json.dumps({
    "tokens_equal": bool(tok_eq),
    "logprob_err": lp_err,
    "decode_execs": sched.compile_counts["decode_step"],
    "n_devices": len(jax.devices()),
}))
"""


def _run_sub(script: str) -> dict:
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
def test_quant_pool_matches_single_device_under_mesh():
    """int8 pool with KV capacity sharded over a real 2-device 'model'
    mesh: greedy tokens match the single-device int8 pool exactly (the
    shard-local scale scatter + in-shard dequant compose with
    flash-decoding partials), ONE decode executable."""
    res = _run_sub(_QUANT_MESH_SCRIPT)
    assert res["n_devices"] == 2, res
    assert res["tokens_equal"], res
    assert res["logprob_err"] < 1e-4, res
    assert res["decode_execs"] == 1, res
