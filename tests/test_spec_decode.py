"""Speculative decoding in the resident pool (serving/scheduler.py
``spec_k > 0`` + serving/spec.py): per-request token/logprob parity with
the non-speculative pool AND standalone generate (greedy and sampled,
dense and paged layouts, with and without a serving mesh), the
zero-recompile contract for the ONE multi-token verify executable across
draft/accept/slot churn, the ragged-accept state property (a verify tick
accepting ``a`` drafts leaves the pool in the state ``a+1`` sequential
decode ticks produce), page headroom + reclamation, the recurrent-stack
rejection, and per-request latency stats.

The state property is checked with hypothesis against a *scripted*
drafter that forces an exact accept length per tick: integer state
(tokens, frontiers, rng folds, page tables, allocator accounting) must
be bitwise identical to the sequential pool's; float payloads (KV rows,
logprobs) are compared at f32-ULP tolerance — the verify executable
batches (S, k+1) positions where the sequential step batches (S, 1), and
XLA reassociates those reductions differently by ~1 ULP. Token choice
is exact because candidate selection (argmax / categorical on the
sequential key schedule) happens on the verify logits themselves."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import stack_config, tiny_config
from repro.serving import FedAttnEngine, NGramDrafter, Request
from repro.serving import paging
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig, LayerSpec


def _engine(cfg, **kw):
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.key(0))
    return FedAttnEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def eng():
    """One attention-stack engine shared across this module — solo and
    pool executables accumulate in its caches (realistic reuse)."""
    return _engine(tiny_config())


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
        )
        np.testing.assert_array_equal(r.tokens, solo.tokens)
        np.testing.assert_allclose(
            r.logprobs, solo.logprobs, atol=1e-5, rtol=1e-5
        )


# ---------------------------------------------------------------------------
# parity + the zero-recompile verify contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_spec_parity_and_zero_recompile(eng, kv_layout):
    """A speculative pool on a churning mixed greedy+sampled trace must be
    token- AND logprob-exact against standalone generate (hence against
    the spec_k=0 pool, whose parity is pinned in test_scheduler.py), in
    both KV layouts, ending the trace with exactly ONE verify executable
    and ZERO sequential decode executables — and a second churning trace
    through the same pool compiles nothing new."""
    reqs = [
        _req(0, 24, 8),
        _req(1, 17, 5, temp=0.7),
        _req(2, 30, 3),
        _req(3, 9, 12, temp=0.9),
    ]
    sched = ContinuousBatchingScheduler(
        eng, max_slots=2, capacity=64, kv_layout=kv_layout, spec_k=2
    )
    res = sched.run(reqs)
    cc = dict(sched.compile_counts)
    assert cc["verify_step"] == 1, cc
    assert cc["decode_step"] == 0, cc  # spec pools never build the 1-tok step
    assert cc["slot_write"] == 1, cc
    _assert_matches_solo(eng, res, reqs)

    st_ = sched.pool_stats()
    assert st_["spec_k"] == 2
    assert st_["verify_ticks"] > 0
    assert 0 <= st_["spec_accepted"] <= st_["spec_drafted"]
    assert 0.0 <= st_["spec_acceptance_rate"] <= 1.0

    # fresh churning trace over the SAME shape buckets, same pool: zero
    # new executables of any kind (snapshot after the solo generates so
    # their own prefill entries don't read as pool recompiles)
    cc = dict(sched.compile_counts)
    reqs2 = [_req(10, 20, 4), _req(11, 28, 6, temp=0.7),
             _req(12, 12, 3), _req(13, 25, 5, temp=0.9)]
    res2 = sched.run(reqs2)
    assert dict(sched.compile_counts) == cc
    _assert_matches_solo(eng, res2, reqs2)


def test_spec_parity_scan_mode():
    """Scan-over-layers lowering: the verify step threads the multi-token
    block through the stacked layer scan; outputs still match solo."""
    cfg = tiny_config(
        n_layers=8,
        pattern=(LayerSpec(), LayerSpec(sync=True)),
        fedattn=FedAttnConfig(n_participants=4, sync_interval=2),
    )
    e = _engine(cfg)
    assert e._plan.n_periods == 4
    reqs = [_req(0, 24, 6, cfg=cfg), _req(1, 12, 4, temp=0.7, cfg=cfg)]
    sched = ContinuousBatchingScheduler(e, max_slots=2, capacity=64, spec_k=3)
    res = sched.run(reqs)
    assert sched.compile_counts["verify_step"] == 1
    _assert_matches_solo(e, res, reqs)


def test_spec_parity_under_serving_mesh(eng):
    """Speculative pool under a (1-shard, in-process) serving mesh: the
    verify step traces through the SPMD flash-decoding path; parity with
    the meshless solo reference must hold. (The multi-device variant is
    the slow subprocess test below.)"""
    from repro.launch.mesh import make_serving_mesh

    e = _engine(tiny_config(), mesh=make_serving_mesh(1))
    reqs = [_req(0, 20, 6), _req(1, 14, 4, temp=0.8)]
    sched = ContinuousBatchingScheduler(e, max_slots=2, capacity=64, spec_k=2)
    res = sched.run(reqs)
    assert sched.compile_counts["verify_step"] == 1
    _assert_matches_solo(eng, res, reqs)


# ---------------------------------------------------------------------------
# validation: recurrent stacks, steps_per_admit, spec_k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["hybrid", "rwkv"])
def test_spec_rejects_recurrent_stacks(kind):
    """SSM/hybrid pools must raise a clear NotImplementedError naming the
    actual blocker: recurrent layers fold tokens into carried state with
    no per-position KV to invalidate, so verify-then-rollback would need
    a recurrent-state checkpoint per draft position."""
    e = _engine(stack_config(kind))
    with pytest.raises(NotImplementedError, match="no per-position KV"):
        ContinuousBatchingScheduler(e, max_slots=2, capacity=32, spec_k=2)
    with pytest.raises(NotImplementedError, match="recurrent-state checkpoint"):
        ContinuousBatchingScheduler(e, max_slots=2, capacity=32, spec_k=1)


def test_spec_knob_validation(eng):
    with pytest.raises(ValueError, match="steps_per_admit == 1"):
        ContinuousBatchingScheduler(
            eng, max_slots=2, capacity=32, spec_k=2, steps_per_admit=3
        )
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatchingScheduler(eng, max_slots=2, capacity=32, spec_k=-1)
    with pytest.raises(ValueError, match="drafter"):
        ContinuousBatchingScheduler(
            eng, max_slots=2, capacity=32, spec_k=2, drafter=object()
        )


# ---------------------------------------------------------------------------
# the n-gram drafter
# ---------------------------------------------------------------------------


def test_ngram_drafter_proposes_continuations():
    d = NGramDrafter()
    state = d.begin([1, 2, 3, 1, 2])
    # trailing 2-gram (1,2) recurs at the start -> propose what followed
    np.testing.assert_array_equal(d.draft(state, 3), [3, 1, 2])
    # short continuations pad by repeating their last token
    state2 = d.begin([5, 6, 5])
    np.testing.assert_array_equal(d.draft(state2, 4), [6, 5, 5, 5])
    # novel tail -> period-1 fallback (repeat the last token)
    d.update(state, np.array([9]))
    np.testing.assert_array_equal(d.draft(state, 2), [9, 9])


# ---------------------------------------------------------------------------
# page headroom + reclamation
# ---------------------------------------------------------------------------


def test_pages_for_request_spec_headroom():
    """Worst case: the last verify tick starts one token short of the
    request span and still writes spec_k draft rows past it — spec_k - 1
    positions beyond L + n_new."""
    assert paging.pages_for_request(10, 6, 8) == paging.pages_for(16, 8)
    assert paging.pages_for_request(10, 6, 8, spec_k=1) == paging.pages_for(16, 8)
    assert paging.pages_for_request(10, 6, 8, spec_k=3) == paging.pages_for(18, 8)
    assert paging.pages_for_request(6, 3, 2, spec_k=3) == 6  # 6+3+2 over ps=2


def test_spec_pool_allocates_headroom_and_reclaims(eng):
    """A speculative admission owns pages for L + n_new + (spec_k - 1)
    tokens (the rejected-draft write span), one page more than the
    non-speculative span here; every page returns to the allocator at
    retirement."""
    sched = ContinuousBatchingScheduler(
        eng, max_slots=2, capacity=16, page_size=2, spec_k=3
    )
    req = _req(0, 6, 6)  # span 12 -> 6 pages; +k-1=2 headroom -> 7 pages
    rid = sched.submit(req)
    sched.step()
    slot = next(s for s, o in enumerate(sched._slots) if o is not None)
    assert len(sched._slots[slot].pages) == 7
    assert sched._alloc.used_pages == 7
    while not sched.done():
        sched.step()
    assert sched._alloc.used_pages == 0  # headroom reclaimed with the rest
    _assert_matches_solo(eng, [sched.pop_result(rid)], [req])


# ---------------------------------------------------------------------------
# latency stats (TTFT / TPOT percentiles)
# ---------------------------------------------------------------------------


def test_latency_stats_recorded(eng):
    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=64)
    sched.run([_req(0, 16, 4), _req(1, 16, 1)])
    ls = sched.latency_stats()
    assert ls["ttft_n"] == 2  # every request gets a first token
    assert ls["tpot_n"] == 1  # only n_new > 1 has a decode phase
    assert 0.0 <= ls["ttft_p50"] <= ls["ttft_p95"]
    assert ls["tpot_p50"] > 0.0
    assert "ttft_p50" in sched.pool_stats()  # surfaced next to pool stats
    sched.latency_stats(reset=True)
    assert sched.latency_stats()["ttft_n"] == 0


# ---------------------------------------------------------------------------
# jaxpr audit coverage of the verify entry point
# ---------------------------------------------------------------------------


def test_jaxpr_audit_traces_verify_entry(eng):
    from repro.analysis import jaxpr_audit

    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=32, spec_k=2)
    entries = jaxpr_audit.trace_scheduler_entries(sched)
    names = [e.name for e in entries]
    assert "scheduler.verify_step" in names
    assert jaxpr_audit.audit_entries(entries) == []
    # audit_engine's pool sweep includes the verify step on attention stacks
    assert jaxpr_audit.audit_engine(eng) == []


# ---------------------------------------------------------------------------
# the ragged-accept state property
# ---------------------------------------------------------------------------


class ScriptedDrafter:
    """Test drafter forcing an exact accept length per verify tick: it
    proposes the TRUE greedy continuation (from a solo generate) for the
    first ``a`` draft positions and a deliberately-wrong token after, so
    verify accepts exactly ``a`` (clamped at the request tail). ``plans``
    maps prompt-token tuples to (reference continuation, accept schedule);
    the schedule's last entry repeats for later ticks."""

    def __init__(self, vocab: int):
        self.vocab = vocab
        self.plans: dict = {}

    def begin(self, tokens):
        key = tuple(int(t) for t in tokens[:-1])  # scheduler appends tok0
        ref, accepts = self.plans[key]
        return {"ref": ref, "accepts": accepts, "n": 1, "tick": 0}

    def draft(self, state, k):
        ref, n = state["ref"], state["n"]
        acc = state["accepts"]
        a = acc[min(state["tick"], len(acc) - 1)]
        state["tick"] += 1
        out = np.empty(k, np.int32)
        for i in range(k):
            true = ref[n + i] if n + i < len(ref) else 0
            out[i] = true if i < a else (true + 1) % self.vocab
        return out

    def update(self, state, tokens):
        state["n"] += len(tokens)


def _slot_kv(sched, slot, span):
    """Logical per-position (K, V) rows of one slot over [0, span), layer
    by layer, read through the slot's own page table (paged) or row
    (dense) of the stacked pool."""
    c = sched.cache
    layers = [jax.tree.map(lambda x: x[n], layer)
              for n in range(sched._plan.n_periods) for layer in c["stacked"]]
    out = []
    for layer in layers + list(c["remainder"]):
        if "pk" in layer:
            pk, pv = np.asarray(layer["pk"]), np.asarray(layer["pv"])
            ps = pk.shape[1]
            tbl = sched._pages_tbl[slot]
            k = np.stack([pk[tbl[p // ps], p % ps] for p in range(span)])
            v = np.stack([pv[tbl[p // ps], p % ps] for p in range(span)])
        else:
            k = np.asarray(layer["k"])[slot, :span]
            v = np.asarray(layer["v"])[slot, :span]
        out.append((k, v))
    return out


def _assert_same_slot_state(spec, slot, seq, L):
    """Integer state bitwise, float payloads at f32-ULP tolerance."""
    assert int(spec._write_pos[slot]) == int(seq._write_pos[0])
    assert int(spec._fold[slot]) == int(seq._fold[0])
    assert int(spec._tok[slot]) == int(seq._tok[0])
    a, b = spec._slots[slot], seq._slots[0]
    assert a.tokens == b.tokens
    assert a.n_emitted == b.n_emitted
    np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-5, rtol=1e-5)
    span = int(spec._write_pos[slot])  # KV written for [0, frontier)
    # identical page-table occupancy (physical ids may differ)
    n_pages = paging.pages_for(span, spec.page_size)
    assert np.all(spec._pages_tbl[slot][:n_pages] < spec.num_pages)
    for (ks, vs), (kq, vq) in zip(
        _slot_kv(spec, slot, span), _slot_kv(seq, 0, span)
    ):
        np.testing.assert_allclose(ks, kq, atol=2e-6, rtol=0)
        np.testing.assert_allclose(vs, vq, atol=2e-6, rtol=0)


_PROP: dict = {}


def _prop_pools():
    """Module-cached pools for the hypothesis sweep (a fresh scheduler per
    example would re-jit the verify/decode closures every time)."""
    if not _PROP:
        cfg = tiny_config()
        e = _engine(cfg)
        dr = ScriptedDrafter(cfg.vocab_size)
        _PROP["cfg"], _PROP["eng"], _PROP["drafter"] = cfg, e, dr
        _PROP["spec"] = ContinuousBatchingScheduler(
            e, max_slots=2, capacity=32, page_size=4, spec_k=3, drafter=dr
        )
        _PROP["seq"] = ContinuousBatchingScheduler(
            e, max_slots=1, capacity=32, page_size=4
        )
    return _PROP


@settings(max_examples=10, deadline=None)
@given(
    L=st.integers(5, 12),
    a1=st.integers(0, 3),
    a2=st.integers(0, 3),
    n_new=st.integers(6, 9),
)
def test_ragged_accept_matches_sequential_state(L, a1, a2, n_new):
    """THE speculative state property: after a verify tick that accepts
    ``a`` drafts, the pool's per-slot state (frontier, rng fold, emitted
    tokens/logprobs, page tables, logical KV rows) is the state a
    sequential spec_k=0 pool reaches in ``a+1`` single-token ticks —
    swept over accept lengths straddling page boundaries (page_size=4,
    k=3) and over slot churn mid-speculation (a sibling request retires
    on the first verify tick and a third admits into its slot on the
    second). Rejected draft rows live PAST the frontier and are outside
    the compared span by construction — the contract is that they are
    invisible, not zeroed (kernels/core docstring)."""
    p = _prop_pools()
    spec, seq, e, dr = p["spec"], p["seq"], p["eng"], p["drafter"]
    cfg = p["cfg"]

    toks_a = jax.random.randint(
        jax.random.key(1000 + L), (L,), 0, cfg.vocab_size)
    toks_b = jax.random.randint(jax.random.key(2000 + L), (6,), 0, cfg.vocab_size)
    toks_c = jax.random.randint(jax.random.key(3000 + L), (8,), 0, cfg.vocab_size)
    ref_a = np.asarray(e.generate(toks_a[None], n_new).tokens)[0].tolist()
    ref_b = np.asarray(e.generate(toks_b[None], 2).tokens)[0].tolist()
    ref_c = np.asarray(e.generate(toks_c[None], 3).tokens)[0].tolist()
    dr.plans = {
        tuple(np.asarray(toks_a).tolist()): (ref_a, [a1, a2]),
        tuple(np.asarray(toks_b).tolist()): (ref_b, [3]),
        tuple(np.asarray(toks_c).tolist()): (ref_c, [a2]),
    }

    rid_a = spec.submit(Request(tokens=toks_a, n_new=n_new))
    rid_b = spec.submit(Request(tokens=toks_b, n_new=2))
    spec.step()  # admit A+B, verify tick 1 — B retires (1 token left)
    assert spec.pop_result(rid_b) is not None
    take1 = min(a1 + 1, n_new - 1)
    slot_a = next(
        s for s, o in enumerate(spec._slots)
        if o is not None and o.req_id == rid_a
    )

    rid_s = seq.submit(Request(tokens=toks_a, n_new=n_new))
    for _ in range(take1):
        seq.step()
    _assert_same_slot_state(spec, slot_a, seq, L)

    rid_c = spec.submit(Request(tokens=toks_c, n_new=3))
    spec.step()  # C admits into B's slot mid-speculation; verify tick 2
    take2 = min(a2 + 1, n_new - 1 - take1)
    for _ in range(take2):
        seq.step()
    if spec._slots[slot_a] is not None:
        assert seq._slots[0] is not None  # both retire on the same tick
        _assert_same_slot_state(spec, slot_a, seq, L)

    while not spec.done():
        spec.step()
    while not seq.done():
        seq.step()
    res_a, res_s = spec.pop_result(rid_a), seq.pop_result(rid_s)
    res_c = spec.pop_result(rid_c)
    np.testing.assert_array_equal(res_a.tokens, res_s.tokens)
    np.testing.assert_array_equal(res_a.tokens[0], ref_a)
    np.testing.assert_allclose(
        res_a.logprobs, res_s.logprobs, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(res_c.tokens[0], ref_c)
    assert spec._alloc.used_pages == 0 and seq._alloc.used_pages == 0


# ---------------------------------------------------------------------------
# multi-device mesh parity (slow subprocess, 2 fake CPU devices)
# ---------------------------------------------------------------------------

_SPEC_MESH_SCRIPT = r"""
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

def req(i, L, n_new, temp=0.0):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, cfg.vocab_size)
    rng = jax.random.key(100 + i) if temp > 0 else None
    return Request(tokens=toks, n_new=n_new, temperature=temp, rng=rng)

reqs = [req(0, 24, 6), req(1, 17, 4, temp=0.7), req(2, 30, 3), req(3, 9, 8)]

single = FedAttnEngine(cfg, params)
base = single.generate_many(reqs, max_slots=2, capacity=64)

mesh = jax.make_mesh((2,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
eng = FedAttnEngine(cfg, params, mesh=mesh)
sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=64, spec_k=2)
got = sched.run(reqs)
cc = dict(sched.compile_counts)

tok_eq = all(np.array_equal(a.tokens, b.tokens) for a, b in zip(base, got))
lp_err = max(
    float(np.abs(a.logprobs - b.logprobs).max()) for a, b in zip(base, got)
)
print(json.dumps({
    "tokens_equal": bool(tok_eq),
    "logprob_err": lp_err,
    "verify_execs": cc["verify_step"],
    "decode_execs": cc["decode_step"],
    "n_devices": len(jax.devices()),
}))
"""


def _run(script: str) -> dict:
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
def test_spec_pooled_decode_matches_single_device_mesh():
    """Speculative pool under a real 2-device mesh (KV capacity sharded
    over 'model'): tokens match the meshless non-speculative pool exactly,
    logprobs to fp tolerance, ONE verify executable, ZERO decode-step
    executables."""
    res = _run(_SPEC_MESH_SCRIPT)
    assert res["n_devices"] == 2, res
    assert res["tokens_equal"], res
    assert res["logprob_err"] < 1e-4, res
    assert res["verify_execs"] == 1, res
    assert res["decode_execs"] == 0, res
