"""Continuous-batching scheduler (serving/scheduler.py): per-request
token/logprob parity with standalone generate (greedy AND sampled,
one-period AND periodic scan plans, sparse KV exchange, heterogeneous partitions),
the zero-recompile contract (ONE resident decode executable across a
trace whose active-slot set changes every step), slot reuse, result
ordering, and capacity validation.

The core contracts — parity, churn without recompiles, coalesced
one-executable admission — are pinned over ALL THREE stack kinds
(attention / rwkv / mamba-hybrid) from one parametrized fixture
(``stack_eng``, marked ``stack_sweep``): since the recurrence validity
contract there is a single admission path, so the pins must hold
uniformly, including per-slot SSM/conv/token-shift state in the pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import STACK_KINDS, stack_config, tiny_config
from repro.serving import FedAttnEngine, Request
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig, LayerSpec


def _engine(cfg, **kw):
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.key(0))
    return FedAttnEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def eng():
    """One engine for every default-config test — solo-generate and pool
    executables accumulate in its caches across tests (realistic reuse)."""
    return _engine(tiny_config())


@pytest.fixture(scope="module", params=STACK_KINDS)
def stack_eng(request):
    """THE stack-kind sweep: one shared engine per stack kind (attention /
    rwkv / mamba-hybrid), reused across the parity/churn/compile-count
    tests below so executables accumulate realistically per kind."""
    return _engine(stack_config(request.param))


def _req(i, L, n_new, temp=0.0, cfg=None):
    cfg = cfg or tiny_config()
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, cfg.vocab_size)
    rng = jax.random.key(100 + i) if temp > 0 else None
    return Request(tokens=toks, n_new=n_new, temperature=temp, rng=rng)


def _assert_matches_solo(eng, results, reqs):
    for r, req in zip(results, reqs):
        solo = eng.generate(
            req.tokens[None], req.n_new,
            temperature=req.temperature, rng=req.rng,
            partition=req.partition,
        )
        np.testing.assert_array_equal(r.tokens, solo.tokens)
        np.testing.assert_allclose(
            r.logprobs, solo.logprobs, atol=1e-5, rtol=1e-5
        )
        assert r.prefill_comm_bytes == solo.prefill_comm_bytes


@pytest.mark.stack_sweep
def test_parity_mixed_greedy_and_sampled(stack_eng):
    """4 mixed-length requests through a 2-slot pool (forcing mid-flight
    retire + re-admit) must each match a standalone generate exactly —
    greedy and sampled, including the first (prefill) token — on every
    stack kind (recurrent slots carry per-slot SSM/conv/shift state)."""
    reqs = [
        _req(0, 24, 8),
        _req(1, 17, 5, temp=0.7),
        _req(2, 30, 3),
        _req(3, 9, 12, temp=0.9),
    ]
    res = stack_eng.generate_many(reqs, max_slots=2, capacity=64)
    assert [r.tokens.shape for r in res] == [(1, 8), (1, 5), (1, 3), (1, 12)]
    _assert_matches_solo(stack_eng, res, reqs)


def test_parity_scan_mode_fused_steps():
    """Scan-over-layers pool + steps_per_admit>1: finished slots coast a
    few surplus steps before retiring; outputs still match standalone."""
    cfg = tiny_config(
        n_layers=8,
        pattern=(LayerSpec(), LayerSpec(sync=True)),
        fedattn=FedAttnConfig(n_participants=4, sync_interval=2),
    )
    e = _engine(cfg)
    assert e._plan.n_periods == 4
    reqs = [_req(0, 24, 8, cfg=cfg), _req(1, 12, 5, temp=0.7, cfg=cfg),
            _req(2, 20, 3, cfg=cfg)]
    sched = ContinuousBatchingScheduler(
        e, max_slots=2, capacity=64, steps_per_admit=3
    )
    res = sched.run(reqs)
    assert sched.compile_counts["decode_step"] == 1
    _assert_matches_solo(e, res, reqs)


def test_parity_sparse_kv_and_partition(eng):
    """Request rng seeds the sparse-KV contribution masks and per-request
    partitions change the per-slot kv segment rows — both must flow through
    the pool's traced arguments, not recompile or go stale."""
    from repro.core.partition import Partition

    cfg = tiny_config(
        fedattn=FedAttnConfig(
            n_participants=4, sync_interval=2,
            kv_exchange_ratio=0.5, kv_selection="strided",
        ),
    )
    e = _engine(cfg)
    reqs = [
        Request(
            tokens=jax.random.randint(jax.random.key(5), (24,), 0, cfg.vocab_size),
            n_new=6, rng=jax.random.key(7),
        ),
        Request(
            tokens=jax.random.randint(jax.random.key(6), (24,), 0, cfg.vocab_size),
            n_new=6, rng=jax.random.key(8),
            partition=Partition.from_sizes([12, 4, 4, 4]),
        ),
    ]
    sched = ContinuousBatchingScheduler(e, max_slots=2, capacity=64)
    res = sched.run(reqs)
    assert sched.compile_counts["decode_step"] == 1
    _assert_matches_solo(e, res, reqs)


@pytest.mark.stack_sweep
def test_zero_decode_recompiles_across_churning_trace(stack_eng):
    """Acceptance: staggered n_new makes the active-slot set change every
    step (retire + admit mid-flight); the pool must end the trace with
    exactly ONE decode executable and ONE slot-write executable — slot
    churn with recurrent state never recompiles the resident step."""
    reqs = [_req(i, 10 + 3 * i, 2 + i, temp=0.4 * (i % 2)) for i in range(6)]
    sched = ContinuousBatchingScheduler(stack_eng, max_slots=3, capacity=64)
    res = sched.run(reqs)
    cc = sched.compile_counts
    assert cc["decode_step"] == 1, cc
    assert cc["slot_write"] == 1, cc
    assert len(res) == 6 and all(
        r.tokens.shape == (1, reqs[i].n_new) for i, r in enumerate(res)
    )
    # the same pool serves a fresh trace with zero new executables
    n_prefill = cc["prefill"]
    reqs2 = [_req(10 + i, 11 + 5 * i, 3 + i) for i in range(4)]
    sched.run(reqs2)
    cc2 = sched.compile_counts
    assert cc2["decode_step"] == 1 and cc2["prefill"] == n_prefill, cc2


def test_slot_reuse_does_not_leak_between_occupants(eng):
    """A slot freed by a short request and re-used by a later one must not
    leak stale KV: run the same request first and last in a trace — both
    copies must produce identical outputs."""
    probe = _req(0, 24, 6)
    filler = [_req(i, 14 + i, 8) for i in range(1, 4)]
    res = eng.generate_many([probe] + filler + [probe], max_slots=2,
                            capacity=64)
    np.testing.assert_array_equal(res[0].tokens, res[-1].tokens)
    np.testing.assert_allclose(res[0].logprobs, res[-1].logprobs,
                               atol=1e-6, rtol=1e-6)


def test_n_new_1_request_retires_at_admit(eng):
    """A single-token request completes from its prefill logits alone —
    mirroring generate's n_new==1 path — and frees its slot immediately."""
    reqs = [_req(0, 24, 1), _req(1, 18, 4)]
    res = eng.generate_many(reqs, max_slots=1, capacity=64)
    assert res[0].tokens.shape == (1, 1)
    _assert_matches_solo(eng, res, reqs)


@pytest.mark.stack_sweep
@pytest.mark.parametrize("stack", STACK_KINDS)
def test_admission_coalescing_one_prefill_executable(stack):
    """Same-bucket admissions arriving together must run as ONE B>1
    bucketed prefill — the single admission path, every stack kind: a
    fresh engine serving 4 same-bucket requests through a 4-slot pool ends
    the trace with exactly one prefill executable (the coalesced per-row
    one), and a second identical trace adds zero. For SSM/hybrid stacks
    this is the pin that the legacy one-at-a-time admission (one
    executable per exact L) is gone."""
    e = _engine(stack_config(stack))
    reqs = [_req(i, 20 + i, 4, temp=0.5 * (i % 2)) for i in range(4)]  # all Lp=32
    sched = ContinuousBatchingScheduler(e, max_slots=4, capacity=64)
    res = sched.run(reqs)
    cc = sched.compile_counts
    assert cc["prefill"] == 1, cc  # ONE coalesced (B=4, Lp=32) executable
    assert cc["decode_step"] == 1 and cc["slot_write"] == 1, cc
    sched.run(reqs)
    assert sched.compile_counts == cc
    _assert_matches_solo(e, res, reqs)


def test_ssm_mesh_raise_names_the_state_handoff_blocker():
    """SSM/hybrid pools under a serving mesh still raise — but the message
    must name the ACTUAL remaining blocker: the slot state follows the
    validity/segment contract now; what is missing is composing spmd_ssm's
    inter-shard state hand-off with the capacity-sharded slot pool."""
    from repro.launch.mesh import make_serving_mesh

    e = _engine(stack_config("hybrid"), mesh=make_serving_mesh(1))
    with pytest.raises(NotImplementedError, match="state hand-off"):
        ContinuousBatchingScheduler(e, max_slots=2, capacity=32)


def test_admission_coalescing_reuses_wider_batches():
    """A later, smaller same-bucket group must pad up into the
    already-compiled wider admission executable instead of compiling a new
    one (padding rows are dropped at the slot scatter)."""
    e = _engine(tiny_config())
    sched = ContinuousBatchingScheduler(e, max_slots=4, capacity=64)
    res4 = sched.run([_req(i, 18 + i, 3) for i in range(4)])  # compiles B=4
    n = sched.compile_counts["prefill"]
    reqs2 = [_req(10, 21, 3), _req(11, 24, 3, temp=0.8)]  # group of 2
    res2 = sched.run(reqs2)
    assert sched.compile_counts["prefill"] == n  # padded into the B=4 exec
    _assert_matches_solo(e, res4 + res2, [_req(i, 18 + i, 3) for i in range(4)] + reqs2)


def test_admission_coalescing_mixed_buckets_and_partitions():
    """One tick admitting requests across two L-buckets with per-request
    partitions and sparse-KV rng: each bucket coalesces separately; per-row
    segment/contribution vectors must keep exact solo parity."""
    from repro.core.partition import Partition

    cfg = tiny_config(
        fedattn=FedAttnConfig(
            n_participants=4, sync_interval=2,
            kv_exchange_ratio=0.5, kv_selection="strided",
        ),
    )
    e = _engine(cfg)
    reqs = [
        Request(tokens=jax.random.randint(jax.random.key(20), (20,), 0, cfg.vocab_size),
                n_new=4, rng=jax.random.key(30)),
        Request(tokens=jax.random.randint(jax.random.key(21), (28,), 0, cfg.vocab_size),
                n_new=4, rng=jax.random.key(31),
                partition=Partition.from_sizes([16, 4, 4, 4])),
        Request(tokens=jax.random.randint(jax.random.key(22), (40,), 0, cfg.vocab_size),
                n_new=3, rng=jax.random.key(32), temperature=0.6),
    ]
    sched = ContinuousBatchingScheduler(e, max_slots=4, capacity=64)
    res = sched.run(reqs)
    # buckets {32: 2 reqs, 64: 1 req} -> exactly two prefill executables
    assert sched.compile_counts["prefill"] == 2, sched.compile_counts
    _assert_matches_solo(e, res, reqs)


def test_capacity_validation(eng):
    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=32)
    with pytest.raises(ValueError, match="KV pages"):
        sched.submit(_req(0, 30, 8))  # 30 + 8 > 32
    with pytest.raises(ValueError, match="KV pages"):
        sched.submit(_req(0, 40, 1))  # bucketed prefill 64 > 32
    with pytest.raises(ValueError, match="single-sequence"):
        sched.submit(Request(tokens=jnp.zeros((2, 8), jnp.int32), n_new=2))


def test_arrival_times_respected(eng):
    """Requests with future arrival offsets are not admitted early; the
    trace still completes with correct outputs."""
    reqs = [_req(0, 16, 3), _req(1, 16, 3)]
    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=32)
    res = sched.run(reqs, arrival_times=[0.0, 0.2])
    _assert_matches_solo(eng, res, reqs)
    assert sched.done()
