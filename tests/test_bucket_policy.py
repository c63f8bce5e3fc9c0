"""Shape-bucket policy edges: properties of _next_pow2 / bucket_len /
_bucket_new at boundaries (1, exact powers of two, power+1), and the
segment ``-1`` padding sentinel surviving a full generate round-trip at
those boundaries (bucketed-prefill padding must never leak into real
tokens — jit output equals the unpadded eager reference). Since the
recurrence validity contract (models/ssm, tests/test_ssm_masking.py),
SSM/hybrid stacks bucket L exactly like attention stacks — the sentinel
round-trips run over all three stack kinds, through ``generate`` AND the
continuous-batching pool.

Property tests run under real hypothesis in CI and degrade to the
deterministic offline stub elsewhere (see tests/conftest.py)."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import STACK_KINDS as STACKS, stack_config, tiny_config
from repro.serving import FedAttnEngine, Request
from repro.serving.engine import _next_pow2

_ENGINES: dict = {}


def _eng(kind: str = "attn") -> FedAttnEngine:
    """Lazily-built shared engines so property examples and parametrize
    cases reuse compiled executables instead of recompiling per example."""
    if kind not in _ENGINES:
        from repro.models import build_model

        if kind == "none":
            cfg, kw = tiny_config(), {"bucket": "none"}
        else:
            cfg, kw = stack_config(kind), {}
        params = build_model(cfg).init(jax.random.key(0))
        _ENGINES[kind] = FedAttnEngine(cfg, params, **kw)
    return _ENGINES[kind]


# -- _next_pow2 ---------------------------------------------------------------


@given(n=st.integers(min_value=1, max_value=1 << 20))
@settings(max_examples=60)
def test_next_pow2_is_tight_upper_power(n):
    p = _next_pow2(n)
    assert p >= n
    assert p & (p - 1) == 0, f"{p} not a power of two"
    assert p == 1 or p // 2 < n, f"{p} not the TIGHT bucket for {n}"


@given(k=st.integers(min_value=0, max_value=19))
@settings(max_examples=40)
def test_next_pow2_boundaries(k):
    """Exact powers map to themselves; power+1 jumps to the next bucket —
    the two edges where an off-by-one would silently double padded work or
    recompile per length."""
    p = 1 << k
    assert _next_pow2(p) == p
    assert _next_pow2(p + 1) == 2 * p


# -- engine bucket policy -----------------------------------------------------


@given(n=st.integers(min_value=1, max_value=4096))
@settings(max_examples=40, deadline=None)  # first example builds engines
def test_bucket_len_and_new_policy_every_stack(n):
    """pow2 policy on every causal stack kind — attention, hybrid
    (mamba+attn) and pure-rwkv alike: both dims bucket to _next_pow2 (so 1
    stays 1, powers stay put, power+1 doubles). The old SSM L-identity
    carve-out is gone — padded tokens are identity state updates for
    recurrences (the validity contract), not corruption."""
    for kind in STACKS:
        eng = _eng(kind)
        assert eng._bucket_L_ok, kind
        assert eng.bucket_len(n) == _next_pow2(n), kind
        assert eng._bucket_new(n) == _next_pow2(n), kind


@given(n=st.integers(min_value=1, max_value=4096))
@settings(max_examples=20, deadline=None)  # first example builds an engine
def test_bucket_none_is_identity(n):
    """bucket='none' opts out of padding entirely, both dims."""
    assert _eng("none").bucket_len(n) == n
    assert _eng("none")._bucket_new(n) == n


# -- segment -1 sentinel round-trip at bucket boundaries ----------------------

_BOUNDARY_CASES = [
    (8, 2),   # exact power: zero L padding
    (9, 3),   # power+1: maximal L padding (9 -> 16), n_new 3 -> 4
    (7, 1),   # below power; n_new=1 single-token path
    (16, 4),  # exact power both dims
    (17, 5),  # power+1 again, different bucket pair
]


@pytest.mark.stack_sweep
@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("L,n_new", _BOUNDARY_CASES)
def test_sentinel_survives_generate_round_trip(stack, L, n_new):
    """The padded prefill tokens carry segment -1; if any kernel path let
    them become visible — or any recurrence scanned them into its state or
    its conv/token-shift carries — the jitted tokens/logprobs would diverge
    from the unpadded eager reference at exactly these boundary lengths."""
    eng = _eng(stack)
    cfg = eng.config
    toks = jax.random.randint(jax.random.key(L * 100 + n_new), (2, L), 0,
                              cfg.vocab_size)
    r_jit = eng.generate(toks, n_new)
    r_eager = eng.generate(toks, n_new, compile=False)
    np.testing.assert_array_equal(r_jit.tokens, r_eager.tokens)
    np.testing.assert_allclose(
        r_jit.logprobs, r_eager.logprobs, atol=1e-4, rtol=1e-4
    )


@pytest.mark.stack_sweep
@pytest.mark.parametrize("stack", STACKS)
def test_sentinel_survives_pooled_round_trip(stack):
    """Same sentinel contract through the continuous-batching pool: every
    boundary case prefills into a shared slot pool (one scheduler, so one
    resident decode executable) and must match the eager reference — for
    recurrent stacks this also exercises the per-slot SSM/conv/shift state
    rows and the per-row (ragged) admission vectors."""
    from repro.serving.scheduler import ContinuousBatchingScheduler

    eng = _eng(stack)
    cfg = eng.config
    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=32)
    reqs, refs = [], []
    for L, n_new in _BOUNDARY_CASES:
        toks = jax.random.randint(jax.random.key(L * 100 + n_new), (2, L), 0,
                                  cfg.vocab_size)
        reqs.append(Request(tokens=toks[0], n_new=n_new))
        refs.append(eng.generate(toks[:1], n_new, compile=False))
    for res, ref in zip(sched.run(reqs), refs):
        np.testing.assert_array_equal(res.tokens, ref.tokens)
    assert sched.compile_counts["decode_step"] == 1


@pytest.mark.stack_sweep
@pytest.mark.parametrize("stack", STACKS)
def test_pow2_vs_none_token_and_logprob_exact(stack):
    """Acceptance: bucket='pow2' must produce token- and logprob-exact
    results vs bucket='none' — greedy AND sampled — for every stack kind.
    For recurrent stacks this is the end-to-end consequence of padded
    tokens being exact-identity state updates; any leak (state, conv
    window, token-shift carry, attention visibility) shows up here as a
    divergence at the boundary lengths."""
    eng = _eng(stack)
    e_none = FedAttnEngine(eng.config, eng.params, bucket="none")
    for L, n_new, temp in [(9, 3, 0.0), (17, 5, 0.7)]:
        toks = jax.random.randint(jax.random.key(L), (2, L), 0,
                                  eng.config.vocab_size)
        rng = jax.random.key(L + n_new) if temp > 0 else None
        a = eng.generate(toks, n_new, temperature=temp, rng=rng)
        b = e_none.generate(toks, n_new, temperature=temp, rng=rng)
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.logprobs, b.logprobs,
                                   atol=1e-6, rtol=1e-6)


def test_ssm_mixed_length_sweep_compiles_one_prefill_per_bucket():
    """The executable-collapse pin: a mixed-length sweep inside one (Lp,
    n_new) bucket through a FRESH hybrid engine compiles exactly ONE
    prefill and ONE decode executable (the legacy per-exact-L explosion —
    one executable per distinct L — is gone); a second bucket adds exactly
    one more prefill."""
    from repro.models import build_model

    cfg = stack_config("hybrid")
    params = build_model(cfg).init(jax.random.key(0))
    eng = FedAttnEngine(cfg, params)
    for L in (17, 20, 25, 32):  # all bucket to Lp=32
        toks = jax.random.randint(jax.random.key(L), (1, L), 0, cfg.vocab_size)
        eng.generate(toks, 4)
    assert eng.compile_counts == {"prefill": 1, "decode": 1}, eng.compile_counts
    toks = jax.random.randint(jax.random.key(33), (1, 33), 0, cfg.vocab_size)
    eng.generate(toks, 4)  # next bucket (Lp=64)
    assert eng.compile_counts == {"prefill": 2, "decode": 2}, eng.compile_counts
