"""Engine serving drivers: the compiled path (jitted shape-bucketed prefill
plus the jitted lax.scan decode driver, loop- or scan-over-layers) must
reproduce the eager per-token reference exactly (greedy tokens) / to float
tolerance (logprobs), across FedAttn schedules, participant counts, sparse
KV exchange and windowed layers. Also pins the bucketed executable-cache
contract (zero recompiles within a bucket) and the O(period) trace-size
scaling of scan mode. Pins the GenerationResult.logprobs contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from repro.serving import FedAttnEngine
from repro.types import FedAttnConfig, LayerSpec

B, L, N_NEW = 2, 24, 8


def _engine(cfg, **kw):
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.key(0))
    return FedAttnEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def default_engine():
    """One engine for all default-tiny-config tests — also exercises the
    compiled-driver cache across calls with different sampling modes."""
    return _engine(tiny_config())


def _tokens(cfg):
    return jax.random.randint(jax.random.key(1), (B, L), 0, cfg.vocab_size)


def _parity(cfg, eng=None, **gen_kw):
    eng = eng if eng is not None else _engine(cfg)
    toks = _tokens(cfg)
    r_eager = eng.generate(toks, N_NEW, compile=False, **gen_kw)
    r_jit = eng.generate(toks, N_NEW, compile=True, **gen_kw)
    np.testing.assert_array_equal(r_jit.tokens, r_eager.tokens)
    np.testing.assert_allclose(
        r_jit.logprobs, r_eager.logprobs, atol=1e-4, rtol=1e-4
    )
    return r_jit


def test_greedy_parity_multiparticipant(default_engine):
    _parity(tiny_config(), eng=default_engine)  # n_participants=4, sync every 4th layer


def test_greedy_parity_sparse_kv_exchange():
    cfg = tiny_config(
        fedattn=FedAttnConfig(
            n_participants=4, sync_interval=2,
            kv_exchange_ratio=0.5, kv_selection="strided",
        ),
    )
    _parity(cfg, rng=jax.random.key(7))  # rng also seeds contribution masks


def test_greedy_parity_window_layers():
    cfg = tiny_config(
        pattern=(LayerSpec(window=8), LayerSpec(sync=True)),
        n_layers=4,
    )
    _parity(cfg)


def test_sampled_parity(default_engine):
    r = _parity(tiny_config(), eng=default_engine,
                temperature=0.7, rng=jax.random.key(3))
    assert r.logprobs.min() > -np.inf


def test_logprobs_populated_and_consistent(default_engine):
    """logprobs is (B, n_new), finite, and each entry is the model's
    log-softmax at the emitted token — including the FIRST token, whose
    distribution comes from the prefill logits."""
    cfg = tiny_config()
    eng = default_engine
    toks = _tokens(cfg)
    res = eng.generate(toks, N_NEW)
    assert res.logprobs is not None
    assert res.logprobs.shape == (B, N_NEW)
    assert np.isfinite(res.logprobs).all()
    # greedy ⇒ every emitted token is the argmax ⇒ its logprob is the row max
    assert (res.logprobs <= 0.0).all()

    # first-token cross-check against an explicit prefill forward
    ctx = eng.build_context(L)
    logits = eng.model.apply(eng.params, toks, ctx)
    lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    want = np.take_along_axis(
        np.asarray(lp), res.tokens[:, :1].astype(np.int64), axis=-1
    )[:, 0]
    np.testing.assert_allclose(res.logprobs[:, 0], want, atol=1e-4, rtol=1e-4)


def test_n_new_1_shapes(default_engine):
    cfg = tiny_config()
    eng = default_engine
    res = eng.generate(_tokens(cfg), 1)
    assert res.tokens.shape == (B, 1)
    assert res.logprobs.shape == (B, 1)


def test_n_new_1_matches_longer_run(default_engine):
    """The single-token compiled path (which builds no decode driver and no
    decode-template arrays) must emit exactly the first token — and its
    logprob — of an n_new=4 run with the same seed, greedy AND sampled."""
    cfg = tiny_config()
    eng = default_engine
    toks = _tokens(cfg)
    for kw in ({}, dict(temperature=0.7, rng=jax.random.key(3))):
        r1 = eng.generate(toks, 1, **kw)
        r4 = eng.generate(toks, 4, **kw)
        np.testing.assert_array_equal(r1.tokens[:, 0], r4.tokens[:, 0])
        np.testing.assert_allclose(
            r1.logprobs[:, 0], r4.logprobs[:, 0], atol=1e-5, rtol=1e-5
        )


def _schedule_cfgs():
    """The three schedule regimes the compiled prefill must match eager on."""
    return {
        "multiparticipant": (tiny_config(), None),
        "sparse_kv": (
            tiny_config(
                fedattn=FedAttnConfig(
                    n_participants=4, sync_interval=2,
                    kv_exchange_ratio=0.5, kv_selection="random",
                ),
            ),
            jax.random.key(7),
        ),
        "windowed": (
            tiny_config(pattern=(LayerSpec(window=8), LayerSpec(sync=True)), n_layers=4),
            None,
        ),
    }


@pytest.mark.parametrize("regime", ["multiparticipant", "sparse_kv", "windowed"])
def test_prefill_parity_jit_vs_eager(regime):
    """n_new=1 isolates the prefill: the jitted shape-bucketed prefill (L=24
    padded into the 32-bucket with segment -1 sentinels) must reproduce the
    eager per-layer loop's final-position logits distribution."""
    cfg, rng = _schedule_cfgs()[regime]
    eng = _engine(cfg)
    toks = _tokens(cfg)
    r_jit = eng.generate(toks, 1, rng=rng)
    r_eager = eng.generate(toks, 1, rng=rng, compile=False)
    np.testing.assert_array_equal(r_jit.tokens, r_eager.tokens)
    np.testing.assert_allclose(
        r_jit.logprobs, r_eager.logprobs, atol=1e-4, rtol=1e-4
    )
    assert eng.compile_counts["prefill"] == 1


def _deep_cfg(**fed_kw):
    """Period-2 pattern, 8 layers, periodic schedule — scan-plan eligible."""
    return tiny_config(
        n_layers=8,
        pattern=(LayerSpec(), LayerSpec(sync=True)),
        fedattn=FedAttnConfig(n_participants=4, sync_interval=2, **fed_kw),
    )


@pytest.mark.parametrize("regime", ["plain", "sparse_kv", "windowed"])
def test_scan_vs_loop_decode_parity(regime):
    """Scan-over-layers (stacked params + stacked per-period KV caches) must
    match the eager reference's per-layer loop across schedules."""
    from repro.models import build_model

    if regime == "sparse_kv":
        cfg = _deep_cfg(kv_exchange_ratio=0.5, kv_selection="strided")
    elif regime == "windowed":
        cfg = tiny_config(
            n_layers=8,
            pattern=(LayerSpec(window=8), LayerSpec(sync=True)),
            fedattn=FedAttnConfig(n_participants=4, sync_interval=2),
        )
    else:
        cfg = _deep_cfg()
    params = build_model(cfg).init(jax.random.key(0))
    eng = FedAttnEngine(cfg, params)
    assert eng._plan.n_periods == 4
    toks = _tokens(cfg)
    rng = jax.random.key(7)
    r_scan = eng.generate(toks, N_NEW, rng=rng)
    r_eager = eng.generate(toks, N_NEW, rng=rng, compile=False)
    np.testing.assert_array_equal(r_scan.tokens, r_eager.tokens)
    np.testing.assert_allclose(
        r_scan.logprobs, r_eager.logprobs, atol=1e-4, rtol=1e-4
    )


def test_bucket_reuse_no_recompile():
    """Two requests with different L in the same pow2 bucket (and different
    n_new in the same bucket) must share the compiled executables — zero new
    cache entries on the second call — while staying exact vs eager."""
    cfg = tiny_config()
    eng = _engine(cfg)
    toks24 = _tokens(cfg)
    eng.generate(toks24, 5)  # L=24→32 bucket, n_new=5→8 bucket
    assert eng.compile_counts == {"prefill": 1, "decode": 1}
    toks28 = jax.random.randint(jax.random.key(2), (B, 28), 0, cfg.vocab_size)
    r28 = eng.generate(toks28, N_NEW)  # L=28→same bucket, n_new=8→same
    assert eng.compile_counts == {"prefill": 1, "decode": 1}  # no recompile
    r28_eager = eng.generate(toks28, N_NEW, compile=False)
    np.testing.assert_array_equal(r28.tokens, r28_eager.tokens)
    np.testing.assert_allclose(
        r28.logprobs, r28_eager.logprobs, atol=1e-4, rtol=1e-4
    )
    # out-of-bucket length compiles a fresh prefill executable
    toks40 = jax.random.randint(jax.random.key(3), (B, 40), 0, cfg.vocab_size)
    eng.generate(toks40, N_NEW)
    assert eng.compile_counts["prefill"] == 2


def test_bucket_none_policy_exact_shapes():
    cfg = tiny_config()
    eng = _engine(cfg, bucket="none")
    eng.generate(_tokens(cfg), N_NEW)
    toks28 = jax.random.randint(jax.random.key(2), (B, 28), 0, cfg.vocab_size)
    r = eng.generate(toks28, N_NEW)
    assert eng.compile_counts["prefill"] == 2  # exact-shape policy recompiles
    r_eager = eng.generate(toks28, N_NEW, compile=False)
    np.testing.assert_array_equal(r.tokens, r_eager.tokens)


def test_scan_decode_trace_size_is_O_period():
    """Acceptance: the compiled decode driver for a periodic schedule traces
    the layer pattern once — doubling n_layers must not grow the trace
    (O(period)), while a schedule that does not repeat scans its whole
    body as one period, whose trace is O(n_layers)."""
    from repro.models import build_model

    def eng_for(n_layers, periodic=True):
        if periodic:
            pattern = (LayerSpec(), LayerSpec(sync=True))
            fed = FedAttnConfig(n_participants=4, sync_interval=2)
        else:  # one sync, at the last layer: nothing shorter repeats
            pattern = (LayerSpec(),)
            fed = FedAttnConfig(n_participants=4, sync_interval=n_layers)
        cfg = tiny_config(n_layers=n_layers, pattern=pattern, fedattn=fed)
        params = build_model(cfg).init(jax.random.key(0))
        return FedAttnEngine(cfg, params)

    s8 = eng_for(8).decode_trace_size(B, L, N_NEW)
    s16 = eng_for(16).decode_trace_size(B, L, N_NEW)
    whole = eng_for(16, periodic=False)
    assert (whole._plan.period, whole._plan.n_periods) == (16, 1)
    w16 = whole.decode_trace_size(B, L, N_NEW)
    assert s16 < 1.2 * s8, f"scan trace grew with depth: {s8} -> {s16}"
    assert w16 > 2.0 * s16, (
        f"periodic trace not smaller than one period: {s16} vs {w16}")


def test_uniform_H_equal_to_depth_scans_one_period():
    """On the 4-layer homogeneous stack with sync every 4th layer the
    smallest schedule-periodic unit IS the whole body: the plan is one
    period of 4 layers, and the compiled paths serving it match the eager
    reference. Doubling the depth gives the same H=4 schedule two
    periods."""
    from repro.models import build_model

    def eng_for(n_layers):
        cfg = tiny_config(
            n_layers=n_layers, pattern=(LayerSpec(),),
            fedattn=FedAttnConfig(n_participants=4, sync_interval=4),
        )
        params = build_model(cfg).init(jax.random.key(0))
        return FedAttnEngine(cfg, params)

    e4 = eng_for(4)
    assert e4._plan.period == 4 and e4._plan.n_periods == 1
    toks = _tokens(e4.config)
    rng = jax.random.key(7)
    r_scan = e4.generate(toks, N_NEW, rng=rng)
    r_eager = e4.generate(toks, N_NEW, rng=rng, compile=False)
    np.testing.assert_array_equal(r_scan.tokens, r_eager.tokens)
    np.testing.assert_allclose(
        r_scan.logprobs, r_eager.logprobs, atol=1e-4, rtol=1e-4
    )

    e8 = eng_for(8)  # same H=4 schedule, twice the depth -> 2 repetitions
    assert e8._plan.period == 4 and e8._plan.n_periods == 2


def test_compiled_driver_cached_and_partition_safe():
    """The jitted driver is cached per shape key, and a SECOND call with a
    different partition must NOT reuse stale baked-in segment vectors."""
    from repro.core.partition import Partition

    cfg = tiny_config()
    eng = _engine(cfg)
    toks = _tokens(cfg)
    r1 = eng.generate(toks, N_NEW)
    assert len(eng._decode_fns) == 1
    # different partition, same shapes → same compiled fn, different result path
    part = Partition.from_sizes([12, 4, 4, 4])
    r2 = eng.generate(toks, N_NEW, partition=part)
    assert len(eng._decode_fns) == 1  # no recompile for same static key
    r2_eager = eng.generate(toks, N_NEW, partition=part, compile=False)
    np.testing.assert_array_equal(r2.tokens, r2_eager.tokens)
    # sanity: the two partitions genuinely change the computation
    assert not np.allclose(r1.logprobs, r2.logprobs)
