"""The paged pool rides the decode layer scan's carry (models/transformer.
apply_layers_decode_scan): every period of a scan-form pool is one flat
pool, addressed through per-period offset page tables. Pinned here: a
churning paged trace against the eager reference (plain pool) and
against each request served alone (int8 pool), one-period plans serving
like the eager reference, holes that stay holes across period
boundaries, and the static audits that keep the pool out of the scan's
scanned inputs and stacked outputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import stack_config, tiny_config
from repro.analysis import jaxpr_audit as JA
from repro.models import build_model
from repro.serving import FedAttnEngine, Request, paging
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.types import FedAttnConfig, LayerSpec


def _config():
    # period-2 pattern, sync every 2nd layer: ScanPlan period 2, 4 periods
    return tiny_config(
        n_layers=8,
        pattern=(LayerSpec(), LayerSpec(sync=True)),
        fedattn=FedAttnConfig(n_participants=4, sync_interval=2),
    )


@pytest.fixture(scope="module")
def params():
    return build_model(_config()).init(jax.random.key(0))


def _req(i, L, n_new, temp=0.0):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, 97)
    rng = jax.random.key(100 + i) if temp > 0 else None
    return Request(tokens=toks, n_new=n_new, temperature=temp, rng=rng)


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_scan_pool_matches_reference_under_churn(params, kv_quant):
    """Churning greedy + sampled trace over 2 slots with 3 fused sub-steps
    per tick, so finished slots coast on sentinel pages while the carried
    flat pool serves all 4 periods: every request must come out as if no
    other slot or period had touched its pages.

    Against the eager reference, ``generate(compile=False)`` (per-layer
    loop, unpadded f32 cache): tokens equal; logprobs to 1e-4 for the
    plain pool — the same f32 products summed in another order (scan vs
    loop, paged gather vs a dense cache of another width) — and to 1e-2
    for the int8 pool, whose codes round each K/V element by up to
    1/254 of its page-head's amax (about 1.4e-3 on this trace; a period
    that reads another period's pages moves them by 0.4). The int8 pool
    is also held to each request served alone in a one-slot int8 pool of
    the same page size, whose pages hold the same rows under the same
    per-page scales: tokens equal, logprobs to 1e-5 — only the batch
    width of the matmuls differs."""
    reqs = [_req(0, 24, 8), _req(1, 12, 5, temp=0.7), _req(2, 20, 3),
            _req(3, 9, 7), _req(4, 30, 2, temp=0.4)]
    eng = FedAttnEngine(_config(), params)
    assert eng._plan.n_periods == 4
    pool = dict(capacity=48, steps_per_admit=3, page_size=8, kv_quant=kv_quant)
    sched = ContinuousBatchingScheduler(eng, max_slots=2, **pool)
    out = sched.run(reqs)
    assert sched.compile_counts["decode_step"] == 1
    for i, (got, req) in enumerate(zip(out, reqs)):
        eager = eng.generate(req.tokens[None], req.n_new,
                             temperature=req.temperature, rng=req.rng,
                             compile=False)
        refs = [(eager, 1e-4 if kv_quant is None else 1e-2)]
        if kv_quant is not None:
            alone = ContinuousBatchingScheduler(eng, max_slots=1, **pool)
            refs.append((alone.run([req])[0], 1e-5))
        for want, atol in refs:
            np.testing.assert_array_equal(got.tokens, want.tokens,
                                          err_msg=f"req {i}")
            np.testing.assert_allclose(
                got.logprobs, want.logprobs, atol=atol, rtol=atol,
                err_msg=f"req {i}",
            )


@pytest.mark.parametrize("kind", ["attn", "rwkv"])
def test_one_period_plan_serves_like_eager(kind):
    """The default test stack and the rwkv stack (4 layers, one sync at
    the last) repeat no shorter unit: their plan is the whole body as one
    period, and the pool served through it matches the eager reference —
    tokens equal, logprobs to 1e-4 (f32 sums in another order)."""
    cfg = stack_config(kind)
    eng = FedAttnEngine(cfg, build_model(cfg).init(jax.random.key(0)))
    assert (eng._plan.period, eng._plan.n_periods) == (4, 1)
    reqs = [_req(0, 24, 6), _req(1, 12, 4, temp=0.7), _req(2, 17, 5)]
    out = eng.generate_many(reqs, max_slots=2, capacity=40, steps_per_admit=2)
    for i, (got, req) in enumerate(zip(out, reqs)):
        want = eng.generate(
            req.tokens[None], req.n_new, temperature=req.temperature,
            rng=req.rng, compile=False,
        )
        np.testing.assert_array_equal(got.tokens, want.tokens, err_msg=f"req {i}")
        np.testing.assert_allclose(
            got.logprobs, want.logprobs, atol=1e-4, rtol=1e-4,
            err_msg=f"req {i}",
        )


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_all_sentinel_write_leaves_every_period_bitwise(params, kv_quant):
    """A decode step whose page tables are all holes writes nothing: every
    period of the stacked pool comes back bit-identical. (An offset that
    moved the hole of period i onto period i+1's page 0 would land rows
    there.)"""
    eng = FedAttnEngine(_config(), params)
    sched = ContinuousBatchingScheduler(
        eng, max_slots=2, capacity=16, page_size=4, num_pages=6,
        steps_per_admit=2, kv_quant=kv_quant,
    )
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def fill(x):
        if x.dtype == jnp.int8:
            return jax.random.randint(next(keys), x.shape, -100, 100, jnp.int8)
        return jax.random.uniform(next(keys), x.shape, x.dtype, 0.5, 1.5)

    cache = jax.tree.map(fill, sched.cache)
    before = jax.tree.map(np.asarray, cache)
    holes = np.full_like(sched._pages_tbl, sched.num_pages)
    _, _, after = sched._step_fn(2)(
        eng._run_params(), cache, jnp.asarray([3, 7], jnp.int32),
        jnp.asarray([0, 5], jnp.int32), jnp.asarray(sched._fold),
        jnp.asarray(sched._qseg), jnp.asarray(sched._kvseg),
        jnp.asarray(sched._temps), jnp.asarray(sched._sampled),
        jnp.asarray(sched._key_data), jnp.asarray(holes),
    )
    n = 0
    for old_l, new_l in zip(before["stacked"], after["stacked"]):
        for k in ("pk", "pv", "sk", "sv"):
            if k in old_l:
                n += 1
                np.testing.assert_array_equal(
                    np.asarray(new_l[k]), old_l[k], err_msg=k
                )
    assert n == (8 if kv_quant else 4)


def test_stacked_pages_offsets_and_keeps_holes():
    pages = jnp.asarray([[0, 5, 6, 9], [2, 6, 6, 3]], jnp.int32)
    got = paging.stacked_pages(pages, jnp.int32(2), 6, 4)
    np.testing.assert_array_equal(
        np.asarray(got), [[12, 17, 24, 24], [14, 24, 24, 15]]
    )


def test_pool_scan_audit_clean_and_has_teeth(params):
    """The scheduler's decode and verify steps keep every stacked pool leaf
    out of the scans' scanned inputs and stacked outputs; a function that
    scans the pool as an input trips the check."""
    eng = FedAttnEngine(_config(), params)
    sched = ContinuousBatchingScheduler(
        eng, max_slots=2, capacity=32, page_size=8, spec_k=2,
    )
    entries = JA.trace_scheduler_entries(sched)
    assert {"scheduler.decode_step", "scheduler.verify_step"} <= {
        e.name for e in entries
    }
    assert JA.pool_carry_issues(sched, entries) == []

    shapes = JA.pool_leaf_shapes(sched.cache["stacked"])
    pool = sched.cache["stacked"][0]["pk"]
    assert tuple(pool.shape) in shapes

    def scanned(pool):
        def body(c, p):
            return c + p.sum(), p * 2.0
        return jax.lax.scan(body, jnp.float32(0.0), pool)

    hits = JA.pool_scan_issues("fixture", jax.jit(scanned).trace(pool), shapes)
    assert {i.check for i in hits} == {"pool_scan"}
    assert len(hits) == 2  # the scanned input and the stacked output


def test_fused_audit_keeps_teeth_on_scan_form_pool(params):
    """Flat view and the fused-step ban: the pallas engine's scan-form
    step reads no page through an XLA gather; its xla twin gathers from
    the flat pool and trips the ban."""
    cfg = _config()
    assert JA.audit_fused_decode(
        FedAttnEngine(cfg, params, backend="pallas")
    ) == []
    sched = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params), max_slots=2, capacity=32,
        kv_layout="paged", page_size=8,
    )
    assert sched._plan is not None and sched._plan.n_periods >= 2
    step = next(e for e in JA.trace_scheduler_entries(sched)
                if e.name == "scheduler.decode_step")
    hits = JA.pool_gather_issues(step.name, step.traced, min_pool_rank=5)
    assert hits and all(i.check == "pool_gather" for i in hits)
