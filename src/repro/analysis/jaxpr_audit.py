"""Jaxpr audits: trace every jitted serving entry point, verify contracts.

PRs 2-5 left the serving layer with guarantees that only show up as
*absences* — no f64 op ever enters a kernel, no host callback hides in a
hot loop, trace size stays O(period) under a scan plan, the KV pool is
donated on accelerators, and everything that varies per request is a traced
argument (never a closure-captured buffer).  A failing case is invisible to
unit tests until it costs memory or a recompile in production.  This module
checks them **statically**: each entry point is traced with
``jax.jit(...).trace`` (abstract evaluation — *no compilation, no
execution*) and the resulting jaxpr is walked.

Entry points audited (the compiled serving surface):

* ``engine.prefill``          — bucketed single-request prefill
* ``engine.prefill_per_row``  — coalesced-admission per-row prefill
* ``engine.suffix_prefill``   — suffix-only prefill over gathered prefix
                                pages (paged pools with a prefix cache)
* ``engine.decode``           — the multi-token decode driver
* ``scheduler.decode_step``   — THE resident pooled decode step (traced
                                with per-slot page tables when the pool
                                is block-paged — the paged gather path)
* ``scheduler.verify_step``   — the speculative multi-token verify step
                                (pools built with ``spec_k > 0``; traced
                                with per-slot draft blocks — same pool
                                donation contract as decode_step)
* ``scheduler.slot_write``    — the admission slot-scatter (page-table
                                routed under the paged layout)
* ``scheduler.admit_finish``  — the fused first-token sampler

With an engine carrying a mesh, the scheduler entries trace under the
SPMD scope, so the mesh-pooled step is audited in its shard_map form.

Checks per entry point:

``f64``        no float64 (or complex128) abstract value anywhere in the
               jaxpr, including sub-jaxprs (scan/cond/pjit bodies).
``callback``   no host-callback primitives (pure/io/debug callbacks) — a
               hidden host round-trip per decode step.
``donation``   the declared ``donate_argnums`` equal
               :func:`repro.serving.engine._donation_for_backend` applied
               to the entry's cache/pool operands — the static sibling of
               "the pool updates in place on accelerators".
``consts``     no closure-captured concrete array above a byte threshold:
               weights-scale consts mean params/cache were baked into the
               executable instead of passed as traced args (the static
               sibling of the zero-recompile guarantee — a baked-in buffer
               forces a retrace per buffer identity).
``scaling``    (:func:`audit_trace_scaling`) trace size grows by < ``tol``
               when the layer count doubles under a scan plan — the
               generalization of PR 2's single jaxpr-size pin to every
               entry point.
``pool_gather`` (:func:`audit_fused_decode`, pallas-backend engines) no
               ``gather`` primitive whose operand is a full pool-shaped
               buffer anywhere in the fused decode/verify step — the
               whole point of the fused kernel (kernels/flash_decode) is
               that pages are loaded *inside* the kernel through the
               scalar-prefetched page table; a pool-shaped gather means
               the step silently fell back to the densify-then-attend
               read path.
``pool_scan``  (:func:`pool_carry_issues`, paged pools in scan form on one
               device) no ``scan`` equation of the decode/verify step takes
               a stacked pool leaf (``pk``/``pv``/``sk``/``sv``) as a
               scanned input or emits one as a stacked output — the pool
               rides the layer scan's carry and is written in place
               (models/transformer.apply_layers_decode_scan); a scanned
               pool slices out and restacks every period's pages each step.

Usage: ``python -m repro.analysis --jaxpr`` or the parametrized
tier-1 test (tests/test_analysis.py) which sweeps every config in
``src/repro/configs/``.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Callable, Iterable, Optional

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

#: default ceiling for closure-captured consts (bytes) — far above the
#: index vectors executables legitimately bake in (O(capacity) int32), far
#: below any params/cache leaf at serving scale.
MAX_CONST_BYTES = 1 << 20

_CALLBACK_PRIMS = ("callback", "outside_call", "infeed", "outfeed")


@dataclass(frozen=True)
class AuditIssue:
    """One contract violation found in a traced entry point."""

    entry: str
    check: str  # f64 | callback | donation | consts | scaling | pool_*
    detail: str

    def __str__(self) -> str:
        return f"[{self.entry}] {self.check}: {self.detail}"


@dataclass
class EntryPoint:
    """A traced serving entry point plus its declared donation contract."""

    name: str
    traced: object  # jax.stages.Traced
    cache_argnums: tuple = ()  # operands that must donate on non-CPU


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(params: dict):
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jex_core.Jaxpr):
                    yield item


def iter_eqns(jaxpr):
    """Every equation in a jaxpr, recursing into scan/cond/pjit bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _avals(jaxpr):
    for v in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        if hasattr(v, "aval"):
            yield None, v.aval
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield eqn.primitive.name, v.aval


# ---------------------------------------------------------------------------
# per-entry checks
# ---------------------------------------------------------------------------


def donated_argnums(traced) -> tuple:
    """Positional arguments of a ``jax.stages.Traced`` that donate any of
    their buffers. (``Traced.donate_argnums`` counts flattened leaves, so a
    donated cache pytree shows up as a run of leaf indices, not as its
    argument position.)"""
    return tuple(
        i for i, arg in enumerate(traced.args_info[0])
        if any(leaf.donated for leaf in jax.tree.leaves(arg))
    )


def audit_traced(
    name: str,
    traced,
    *,
    donate_expected: Optional[tuple] = None,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> list[AuditIssue]:
    """Audit one ``jax.stages.Traced`` against the static serving
    contracts."""
    issues: list[AuditIssue] = []
    closed = traced.jaxpr  # ClosedJaxpr
    jaxpr = closed.jaxpr

    # -- f64 ---------------------------------------------------------------
    seen_f64 = set()
    for prim, aval in _avals(jaxpr):
        dt = getattr(aval, "dtype", None)
        if dt is not None and dt in (jnp.float64, jnp.complex128):
            key = (prim, str(dt))
            if key not in seen_f64:
                seen_f64.add(key)
                issues.append(AuditIssue(
                    name, "f64",
                    f"{dt} value {'in primitive ' + prim if prim else 'at the jaxpr boundary'}"
                    " — serving math is f32/bf16 + int32 only",
                ))

    # -- host callbacks ----------------------------------------------------
    for eqn in iter_eqns(jaxpr):
        pname = eqn.primitive.name
        if any(tok in pname for tok in _CALLBACK_PRIMS):
            issues.append(AuditIssue(
                name, "callback",
                f"host-callback primitive {pname!r} in the traced body — "
                "a device→host round trip per step",
            ))

    # -- donation ----------------------------------------------------------
    if donate_expected is not None:
        declared = donated_argnums(traced)
        expected = tuple(sorted(donate_expected))
        if declared != expected:
            issues.append(AuditIssue(
                name, "donation",
                f"declared donate_argnums {declared} != expected {expected} "
                "(repro.serving.engine._donation_for_backend — KV pool/cache "
                "operands must donate on non-CPU backends)",
            ))

    # -- closure-captured consts -------------------------------------------
    for const in closed.consts:
        arr = np.asarray(const) if not hasattr(const, "nbytes") else const
        nbytes = getattr(arr, "nbytes", 0)
        if nbytes > max_const_bytes:
            issues.append(AuditIssue(
                name, "consts",
                f"closure-captured concrete array of {nbytes} bytes "
                f"(shape {getattr(arr, 'shape', '?')}) baked into the "
                "executable — the contract says traced-arg (zero-recompile "
                "guarantee)",
            ))
    return issues


def executable_cache_size(fn) -> Optional[int]:
    """Number of compiled executables held by a jitted fn (None if the JAX
    version does not expose it). The audit itself must leave this at 0 —
    tracing never compiles."""
    probe = getattr(fn, "_cache_size", None)
    return probe() if callable(probe) else None


# ---------------------------------------------------------------------------
# entry-point construction (mirrors engine.generate / scheduler.step)
# ---------------------------------------------------------------------------


def trace_engine_entries(
    engine, *, B: int = 1, L: int = 8, n_new: int = 4, sampled: bool = False,
    per_row_B: int = 2,
) -> list[EntryPoint]:
    """Trace the engine's compiled surface at small shapes: bucketed
    prefill, per-row coalesced prefill, and the decode driver. Argument
    construction mirrors ``generate``/``_admit_group`` exactly — the audit
    sees the same executables serving does."""
    Lp = engine.bucket_len(L)
    Nb = engine._bucket_new(n_new)
    capacity = Lp + Nb
    plan = engine._plan
    params = engine._run_params()
    ctx = engine.build_context(L)
    d0 = ctx.decode_template(capacity)
    entries: list[EntryPoint] = []

    cache = engine.model.init_cache(B, capacity, plan=plan)
    fn = engine._prefill_fn(B, Lp, capacity, None, False)
    traced = fn.trace(
        params, cache, jnp.zeros((B, Lp), jnp.int32), jnp.int32(L),
        jnp.arange(Lp, dtype=jnp.int32), jnp.zeros((Lp,), jnp.int32),
        d0.kv_positions, d0.kv_segments, None, None,
    )
    entries.append(EntryPoint("engine.prefill", traced, (1,)))

    Bp = per_row_B
    cache_p = engine.model.init_cache(Bp, capacity, plan=plan)
    fn = engine._prefill_fn(Bp, Lp, capacity, None, False, per_row=True)
    traced = fn.trace(
        params, cache_p, jnp.zeros((Bp, Lp), jnp.int32),
        jnp.full((Bp,), L, jnp.int32), jnp.arange(Lp, dtype=jnp.int32),
        jnp.zeros((Bp, Lp), jnp.int32),
        jnp.arange(capacity, dtype=jnp.int32),
        jnp.zeros((Bp, capacity), jnp.int32), None, None,
    )
    entries.append(EntryPoint("engine.prefill_per_row", traced, (1,)))

    if n_new > 1:
        fn = engine._decode_fn(B, capacity, Nb, sampled)
        traced = fn.trace(
            params, cache, jnp.zeros((B,), jnp.int32), jnp.int32(L),
            jax.random.key(0), jnp.float32(1.0),
            d0.positions, d0.segments, d0.kv_positions, d0.kv_segments,
        )
        entries.append(EntryPoint("engine.decode", traced, (1,)))
    return entries


def trace_scheduler_entries(scheduler) -> list[EntryPoint]:
    """Trace the pool's compiled surface: the resident decode step, the
    slot-write scatter, and the fused admission sampler.  With a serving
    mesh on the engine, tracing runs under the SPMD scope — the mesh-pooled
    (shard_map flash-decoding) step is what gets audited."""
    sched = scheduler
    eng = sched.engine
    C = sched._cap  # page-padded working capacity (== capacity when dense)
    params = eng._run_params()
    entries: list[EntryPoint] = []

    paged = sched._paged
    with sched._spmd_scope():
        fn = sched._step_fn(sched.steps_per_admit)
        step_args = [
            params, sched.cache, jnp.asarray(sched._tok),
            jnp.asarray(sched._write_pos), jnp.asarray(sched._fold),
            jnp.asarray(sched._qseg), jnp.asarray(sched._kvseg),
            jnp.asarray(sched._temps), jnp.asarray(sched._sampled),
            jnp.asarray(sched._key_data),
        ]
        if paged:
            # the paged gather step: per-slot page tables are traced DATA
            step_args.append(jnp.asarray(sched._pages_tbl))
        traced = fn.trace(*step_args)
    entries.append(EntryPoint("scheduler.decode_step", traced, (1,)))

    if getattr(sched, "spec_k", 0) > 0:
        # speculative verify: the same pooled forward at k+1 query
        # positions per slot; draft tokens are traced data like the rest
        with sched._spmd_scope():
            fn = sched._verify_fn()
            vargs = [
                params, sched.cache, jnp.asarray(sched._tok),
                jnp.zeros((sched.max_slots, sched.spec_k), jnp.int32),
                jnp.asarray(sched._write_pos), jnp.asarray(sched._fold),
                jnp.asarray(sched._qseg), jnp.asarray(sched._kvseg),
                jnp.asarray(sched._temps), jnp.asarray(sched._sampled),
                jnp.asarray(sched._key_data),
            ]
            if paged:
                vargs.append(jnp.asarray(sched._pages_tbl))
            traced = fn.trace(*vargs)
        entries.append(EntryPoint("scheduler.verify_step", traced, (1,)))

    one = eng.model.init_cache(1, C, plan=sched._plan)
    fn = sched._slot_write_fn()
    if paged:
        traced = fn.trace(
            sched.cache, one, jnp.zeros((1,), jnp.int32),
            jnp.full((1, sched._pp), sched.num_pages, jnp.int32),
        )
    else:
        traced = fn.trace(sched.cache, one, jnp.zeros((1,), jnp.int32))
    entries.append(EntryPoint("scheduler.slot_write", traced, (0,)))

    fn = sched._admit_finish_fn()
    traced = fn.trace(
        jnp.zeros((1, eng.config.vocab_size), jnp.float32),
        jnp.ones((1,), jnp.float32), jnp.asarray(sched._key_data[:1]),
        jnp.zeros((1,), bool),
    )
    entries.append(EntryPoint("scheduler.admit_finish", traced, ()))

    if paged and all(s.kind == "attn" for s in eng.config.layer_specs()):
        # suffix-only prefill (prefix-cache hits): cached prefix KV is
        # gathered from the pool through source page tables; write
        # frontiers are traced per-row, the pool is NOT donated (the
        # caller keeps reading it)
        Ls = min(C, eng.bucket_len(2))
        fn = eng._suffix_prefill_fn(1, Ls, C, None)
        traced = fn.trace(
            params, sched.cache,
            jnp.full((1, sched._pp), sched.num_pages, jnp.int32),
            jnp.zeros((1, Ls), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, Ls), jnp.int32),
            jnp.arange(C, dtype=jnp.int32),
            jnp.zeros((1, C), jnp.int32), None,
        )
        entries.append(EntryPoint("engine.suffix_prefill", traced, ()))
    return entries


# ---------------------------------------------------------------------------
# whole-stack audits
# ---------------------------------------------------------------------------


def audit_entries(
    entries: Iterable[EntryPoint], *, backend: Optional[str] = None,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> list[AuditIssue]:
    from repro.serving.engine import _donation_for_backend

    issues: list[AuditIssue] = []
    for e in entries:
        issues.extend(audit_traced(
            e.name, e.traced,
            donate_expected=_donation_for_backend(e.cache_argnums, backend),
            max_const_bytes=max_const_bytes,
        ))
    return issues


def audit_quant_pool(
    scheduler, *, backend: Optional[str] = None,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> list[AuditIssue]:
    """Audit a quantized-pool scheduler's compiled surface.

    On top of the standard per-entry checks (f64/callback/donation/consts —
    the donation contract is UNCHANGED by quantization: scales ride in the
    same cache operand), verifies that the traced decode step and slot
    write actually see the pool in its quantized storage dtype: every
    ``pk``/``pv`` leaf must enter the jaxpr as an int8 (or fp8) aval of
    the pool's rank. A compute-dtype pool aval means something upstream
    dequantized OUTSIDE the gather — the memory win silently evaporated.
    The sync-layer exchange codec is audited through a jitted trace of
    ``core.aggregation.quantized_exchange_roundtrip``: codes must cross
    the trace in the storage dtype, and no f64 sneaks into the rescale.
    """
    from repro.serving import quant

    sched = scheduler
    mode = getattr(sched, "kv_quant", None)
    if mode is None:
        return [AuditIssue(
            "quant_pool", "storage",
            "scheduler has no kv_quant mode — audit_quant_pool only applies "
            "to quantized pools",
        )]
    sd = quant.storage_dtype(mode)
    entries = trace_scheduler_entries(sched)
    issues = audit_entries(
        entries, backend=backend, max_const_bytes=max_const_bytes
    )
    issues.extend(pool_carry_issues(sched, entries))

    pool_rank = 5  # (n_periods, num_pages, page_size, nkv, dh)
    for e in entries:
        if e.name not in ("scheduler.decode_step", "scheduler.slot_write",
                          "scheduler.verify_step"):
            continue
        invars = e.traced.jaxpr.jaxpr.invars
        n_pool = sum(
            1 for v in invars
            if getattr(v.aval, "dtype", None) == sd
            and getattr(v.aval, "ndim", 0) >= pool_rank
        )
        if n_pool == 0:
            issues.append(AuditIssue(
                e.name, "storage",
                f"no {sd} pool buffer of rank >= {pool_rank} among the "
                "traced operands — the pool is entering the executable "
                "dequantized (the quant contract dequantizes INSIDE the "
                "gather, serving/quant.py)",
            ))

    from repro.core.aggregation import quantized_exchange_roundtrip

    cfg = sched.engine.config
    kv = jnp.zeros((1, 8, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    traced = jax.jit(
        lambda k, v: quantized_exchange_roundtrip(k, v, mode)
    ).trace(kv, kv)
    issues.extend(audit_traced(
        "aggregation.quantized_exchange", traced,
        max_const_bytes=max_const_bytes,
    ))
    n_codes = sum(
        1 for _, aval in _avals(traced.jaxpr.jaxpr)
        if getattr(aval, "dtype", None) == sd
    )
    if n_codes == 0:
        issues.append(AuditIssue(
            "aggregation.quantized_exchange", "storage",
            f"no {sd} value anywhere in the exchange round-trip jaxpr — "
            "rows are not actually crossing the wire quantized",
        ))
    return issues


def pool_gather_issues(
    name: str, traced, *, min_pool_rank: int = 4
) -> list[AuditIssue]:
    """Ban dense full-pool gathers from a fused (pallas-backend) step.

    The fused flash-decode contract (kernels/flash_decode) is that the
    paged KV pool is read *in-kernel* through the scalar-prefetched page
    table — page loads are BlockSpec index-map slices, never an XLA
    ``gather`` over the whole pool.  This check walks the traced jaxpr
    (recursing into scan/cond/pjit/pallas bodies) and flags any ``gather``
    equation whose operand aval has the exact shape of a pool-rank traced
    operand.  The XLA read path (ops.paged_attention's densify / page
    gather) trips this by construction — which is what makes the check
    meaningful: it distinguishes the two backends statically.
    """
    jaxpr = traced.jaxpr.jaxpr
    pool_shapes = {
        tuple(v.aval.shape)
        for v in jaxpr.invars
        if getattr(v.aval, "ndim", 0) >= min_pool_rank
    }
    # a scan-form pool is read through its flat view: the stacked leaf
    # (n_periods, N, ...) with its first two dims merged
    pool_shapes |= {
        (s[0] * s[1],) + s[2:] for s in pool_shapes if len(s) >= 5
    }
    issues: list[AuditIssue] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        shape = tuple(getattr(getattr(eqn.invars[0], "aval", None),
                              "shape", ()))
        if shape in pool_shapes:
            issues.append(AuditIssue(
                name, "pool_gather",
                f"gather over a pool-shaped operand {shape} — the fused "
                "step must read pages in-kernel via the prefetched page "
                "table, not densify the pool (kernels/flash_decode)",
            ))
    return issues


def pool_leaf_shapes(cache) -> set:
    """Shapes of the paged pool leaves (``pk``/``pv``/``sk``/``sv``) in a
    cache pytree."""
    from repro.models.transformer import POOL_KEYS

    return {
        tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if getattr(path[-1], "key", None) in POOL_KEYS
    }


def pool_scan_issues(name: str, traced, pool_shapes) -> list[AuditIssue]:
    """Ban stacked pool leaves from the scanned inputs and stacked outputs
    of every ``scan`` equation (recursing into sub-jaxprs). Carried
    operands are allowed — that is where the pool belongs."""
    issues: list[AuditIssue] = []
    for eqn in iter_eqns(traced.jaxpr.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        n_carry = eqn.params["num_carry"]
        xs = eqn.invars[eqn.params["num_consts"] + n_carry:]
        ys = eqn.outvars[n_carry:]
        for role, vs in (("scanned input", xs), ("stacked output", ys)):
            for v in vs:
                shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
                if shape in pool_shapes:
                    issues.append(AuditIssue(
                        name, "pool_scan",
                        f"pool-shaped {role} {shape} of a scan — the pool "
                        "must ride the layer scan's carry and be written "
                        "in place (models/transformer."
                        "apply_layers_decode_scan)",
                    ))
    return issues


def pool_carry_issues(scheduler, entries) -> list[AuditIssue]:
    """:func:`pool_scan_issues` over a scheduler's decode and verify
    entries. Applies to a paged pool on one device; a serving mesh keeps
    its page-sharded pool as a scanned input."""
    sched = scheduler
    if not sched._paged or sched._spmd is not None:
        return []
    shapes = pool_leaf_shapes(sched.cache["stacked"])
    issues: list[AuditIssue] = []
    for e in entries:
        if e.name in ("scheduler.decode_step", "scheduler.verify_step"):
            issues.extend(pool_scan_issues(e.name, e.traced, shapes))
    return issues


def audit_fused_decode(
    engine, *, max_slots: int = 2, capacity: int = 32, page_size: int = 8,
    spec_k: int = 0, backend: Optional[str] = None,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> list[AuditIssue]:
    """Audit the fused Pallas paged flash-decode serving surface.

    Builds a small paged pool over ``engine`` (which must carry
    ``backend='pallas'`` — that is what routes the pooled step through
    :func:`repro.kernels.flash_decode.paged_flash_decode`), traces the
    resident decode step (and the speculative verify step when ``spec_k``)
    and runs the standard static checks **plus** the ``pool_gather`` ban:
    the fused step may not contain an XLA gather over the full pool.
    """
    from repro.serving.scheduler import ContinuousBatchingScheduler

    if getattr(engine, "backend", None) != "pallas":
        return [AuditIssue(
            "fused_decode", "pool_gather",
            f"engine backend {getattr(engine, 'backend', None)!r} is not "
            "'pallas' — audit_fused_decode audits the fused kernel route",
        )]
    sched = ContinuousBatchingScheduler(
        engine, max_slots=max_slots, capacity=capacity,
        kv_layout="paged", page_size=page_size, spec_k=spec_k,
    )
    entries = trace_scheduler_entries(sched)
    issues = audit_entries(
        entries, backend=backend, max_const_bytes=max_const_bytes
    )
    for e in entries:
        if e.name in ("scheduler.decode_step", "scheduler.verify_step"):
            issues.extend(pool_gather_issues(e.name, e.traced, min_pool_rank=5))
    return issues + pool_carry_issues(sched, entries)


def audit_engine(
    engine, *, with_pool: bool = True, B: int = 1, L: int = 8, n_new: int = 4,
    max_slots: int = 2, backend: Optional[str] = None,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> list[AuditIssue]:
    """Trace + audit every serving entry point of an engine (and, with
    ``with_pool``, of a small scheduler pool over it)."""
    entries = trace_engine_entries(engine, B=B, L=L, n_new=n_new)
    issues: list[AuditIssue] = []
    if with_pool:
        from repro.serving.scheduler import ContinuousBatchingScheduler

        cap = engine.bucket_len(L) + engine._bucket_new(n_new)
        spmd = getattr(engine, "spmd", None)
        if spmd is not None:
            n = spmd.mesh.shape[spmd.cache_axes[0]]
            cap += (-cap) % n
        # attention-only stacks also audit the speculative verify entry
        # (spec_k raises on recurrent stacks by design)
        attn_only = all(s.kind == "attn" for s in engine.config.layer_specs())
        sched = ContinuousBatchingScheduler(
            engine, max_slots=max_slots, capacity=cap,
            spec_k=2 if attn_only else 0,
        )
        pool_entries = trace_scheduler_entries(sched)
        entries.extend(pool_entries)
        issues = pool_carry_issues(sched, pool_entries)
    return issues + audit_entries(
        entries, backend=backend, max_const_bytes=max_const_bytes
    )


def _reduced_engine(config, *, seed: int = 0, **engine_kw):
    from repro.models import build_model
    from repro.serving.engine import FedAttnEngine

    model = build_model(config)
    params = model.init(jax.random.key(seed))
    return FedAttnEngine(config, params, **engine_kw)


def audit_arch(
    name: str, *, L: int = 8, n_new: int = 4, backend: Optional[str] = None,
    **reduce_overrides,
) -> list[AuditIssue]:
    """Audit one registered architecture at reduced size.

    Decoder-only stacks trace the full serving surface (engine + pool).
    Encoder-decoder stacks have no serving-engine path yet — their
    encode+decode forward is traced and checked for f64/callbacks/consts
    (no donation contract: there is no resident pool to donate).
    """
    from repro.configs import get_reduced_config

    cfg = get_reduced_config(name, **reduce_overrides)
    cfg = cfg.replace(fedattn=cfg.fedattn.replace(n_participants=2))
    if cfg.is_encoder_decoder:
        return _audit_encdec(name, cfg, L=L)
    engine = _reduced_engine(cfg)
    pool_ok = True
    issues = audit_engine(engine, with_pool=pool_ok, L=L, n_new=n_new,
                          backend=backend)
    if all(s.kind == "attn" for s in cfg.layer_specs()):
        # attention-only stacks also audit the fused pallas route: same
        # static contracts, plus the no-full-pool-gather ban
        issues.extend(audit_fused_decode(
            _reduced_engine(cfg, backend="pallas"), backend=backend
        ))
    return issues


def _audit_encdec(name: str, cfg, *, L: int) -> list[AuditIssue]:
    from repro.launch import steps as S
    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    ctx = S.build_context(cfg, L, encoder=True)
    dec = max(2, L // 2)

    def fwd(params, frames, dec_tokens):
        return model.apply(params, frames, dec_tokens, ctx)

    traced = jax.jit(fwd).trace(
        params,
        jnp.zeros((1, L, cfg.d_model), jnp.float32),
        jnp.zeros((1, dec), jnp.int32),
    )
    return audit_traced(f"{name}.encdec_forward", traced)


def audit_trace_scaling(
    make_engine: Callable[[int], object], *, depths: tuple[int, int] = (2, 4),
    tol: float = 1.6, B: int = 1, L: int = 8, n_new: int = 4,
) -> list[AuditIssue]:
    """The O(period) contract of the decode-path entry points: doubling
    the layer count must leave every entry point's traced-jaxpr size
    within ``tol`` (the scan body is traced once; only bookkeeping may
    grow). ``make_engine(k)`` builds the engine at ``n_layers = period *
    k``. An engine whose plan is one period of the whole body traces every
    layer, so its growth is reported here."""
    sizes: dict[int, dict[str, int]] = {}
    for k in depths:
        engine = make_engine(k)
        sizes[k] = {
            e.name: len(str(e.traced.jaxpr))
            for e in trace_engine_entries(engine, B=B, L=L, n_new=n_new)
        }
    lo, hi = depths[0], depths[-1]
    issues = []
    for entry, base in sizes[lo].items():
        ratio = sizes[hi][entry] / max(base, 1)
        if ratio > tol:
            issues.append(AuditIssue(
                entry, "scaling",
                f"traced jaxpr grew {ratio:.2f}x going from {lo}x to {hi}x "
                f"the layer period (budget {tol}x) — the scan plan is not "
                "keeping trace size O(period)",
            ))
    return issues
