"""Continuous batching over a slotted KV pool — one resident decode
executable serving many interleaved requests.

``FedAttnEngine.generate`` runs one request (batch) to completion: a short
request queued behind a long one waits the whole decode. This module adds
the layer that took centralized engines from batch-at-a-time to production
throughput — request interleaving over a shared KV pool:

* **Block-paged slot pool** (default ``kv_layout='paged'``) — attention KV
  lives in one fixed physical pool of ``(num_pages, page_size)`` blocks per
  layer (``transformer.init_paged_cache``), shared by every slot; each slot
  addresses it through an int32 *page table* row assembled host-side by a
  refcounted allocator (:mod:`repro.serving.paging`). Tables are traced
  DATA — admission/retirement rewrites tables, never shapes, so the
  zero-recompile churn contract is untouched — and pool memory is
  Σ(actual request spans), not ``max_slots × worst-case capacity``.
  ``kv_layout='dense'`` keeps the original ``(max_slots, capacity)`` row
  pool (token/logprob parity between the two layouts is pinned in
  tests/test_paged_serving.py). Recurrent layers (mamba/rwkv) keep
  per-slot SSM/conv/token-shift state rows under the whole-row-replace
  rule in either layout — one pool, every stack kind.
* **Prefix cache** (opt-in ``prefix_cache=True``, paged + attention-only) —
  admitted prompts publish their page runs keyed by the exact bytes that
  determine their KV (tokens, segments, sparse-exchange masks); a later
  admission sharing a cached prefix maps those pages copy-free into its
  table and prefills ONLY the suffix through a dedicated jitted entry
  point (``engine._suffix_prefill_fn`` — traced per-row write frontiers,
  so one executable serves any prefix length). A partially-filled
  boundary page is copied into a fresh page (copy-on-write) so shared
  bytes stay immutable while any reference lives.
* **One resident decode executable** — every scheduler tick runs ONE cached
  jitted step over ALL slots. Everything that distinguishes slots — write
  frontier, query position, segment vectors, temperature, rng key, fold
  step — enters as traced ``(S,)``/``(S, capacity)`` arguments, so the
  executable never recompiles as requests come and go (the
  ``compile_counts`` contract, pinned in tests/test_scheduler.py). Inactive
  slots ride along fully masked (segment ``-1`` — the repo-wide padding
  sentinel — hides their pages from every query, including their own).
* **Coalesced admission** — each tick collects every admissible request,
  groups them by prefill shape bucket, and runs ONE B>1 bucketed prefill
  per group instead of per-request B=1 calls (the batch size itself is
  pow2-padded so group sizes share executables; padding rows replicate a
  real request and are dropped at the slot scatter). Per-row request state
  — real length, partition segments, sparse-exchange masks, sampling —
  rides the batched-vector contract of :mod:`repro.kernels.core`, so one
  executable per (B-bucket, L-bucket) serves any mix of requests. This is
  THE single admission path for every stack kind: recurrent layers consume
  the same per-row segment vectors as validity/reset/shift masks
  (:mod:`repro.models.ssm`), so SSM/hybrid admissions coalesce and
  L-bucket exactly like attention (the per-exact-L executable explosion
  the legacy one-at-a-time SSM admission paid is gone).
* **SPMD pooled decode** — when the engine carries a mesh
  (``FedAttnEngine(mesh=...)``), the pool's KV pages are sharded over the
  mesh's 'model' axis along *capacity* and the resident decode step runs
  the flash-decoding split of :mod:`repro.distributed.spmd_attention`:
  each shard computes partial softmax stats over its slice of every slot,
  one psum combines them, and per-row KV writes land only on the owning
  shard. Admission prefills stay single-device; the slot scatter writes
  into the sharded pool. Per-slot frontiers/positions/segments remain
  traced arguments, so slot churn never recompiles under the mesh either
  (parity + compile counts pinned in tests/test_spmd.py).

* **Per-slot speculative decoding** (opt-in ``spec_k > 0``, attention-only
  stacks) — a host-side drafter (:mod:`repro.serving.spec`; stock
  prompt+output n-gram lookup, no extra weights) proposes ``k`` candidate
  tokens per slot per tick, and ONE bucketed jitted *verify* step scores
  all ``k+1`` positions of every slot in a single forward: each slot
  carries the multi-token query block ``[last_tok, d_1..d_k]`` at traced
  per-row positions ``frontier..frontier+k`` — the same 2-D per-row
  pos/seg visibility contract of :mod:`repro.kernels.core` that bucketed
  prefill rides, so verify reuses THE shared attention core with no new
  mask logic. Per-slot accept lengths (0..k, token-exact acceptance
  against the non-speculative sampling schedule) become ragged frontier
  advances: slot ``s`` moves by ``accept+1`` while its neighbor moves by
  1. Rejected draft KV rows need no scrub — the next tick's ``k+1``-row
  write block starts at the accepted frontier and overwrites every
  rejected row before any query can reach it (decode layers write KV
  before attending; causality hides rows past the live write block), and
  a retiring slot's rows vanish behind the ``PAD_SEGMENT`` kv-segment
  sentinel exactly as in non-speculative retirement. Page allocation
  grows by the worst-case speculative span (paging.pages_for_request)
  and the surplus is reclaimed at retire. Parity is exact: accepted
  tokens ARE the tokens the sequential schedule would emit, so
  speculative pooled decode is token- and logprob-identical to
  ``spec_k=0`` (pinned in tests/test_spec_decode.py).

Per-request parity: a request scheduled through the pool produces the same
tokens/logprobs as a standalone ``engine.generate`` call with the same
seed/partition — decode-step math is row-independent (attention, FFN, norm
and the LM head never mix batch rows) and sampling reproduces generate's
key schedule exactly: token ``m`` uses ``fold_in(request_rng, m)``; greedy
rows take the raw-logit argmax. Pinned in tests/test_scheduler.py for
greedy and sampled requests.

Throughput: each batched step streams the weights once for up to
``max_slots`` tokens, where sequential ``generate`` calls stream them per
request — benchmarks/serving_throughput.py pins the >=2x aggregate tok/s
win on a mixed-length Poisson trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.trace_guard import TraceGuard
from repro.core.partition import Partition
from repro.kernels.core import PAD_SEGMENT
from repro.models import transformer as T
from repro.serving import paging
from repro.serving.engine import (
    GenerationResult, _donation_for_backend, _next_pow2, _token_logprob,
    _verify_candidates,
)
from repro.serving.spec import resolve_drafter


@dataclass
class Request:
    """One decode request: a prompt plus generation knobs — the unit the
    scheduler interleaves. Semantics identical to the matching
    ``engine.generate(tokens[None], n_new, partition=..., temperature=...,
    rng=...)`` call (``rng`` seeds sparse-KV contribution masks AND
    sampling, exactly as in generate; ``temperature > 0`` with ``rng=None``
    is silently greedy — see GenerationResult)."""

    tokens: jnp.ndarray  # (L,) or (1, L) prompt token ids
    n_new: int
    partition: Optional[Partition] = None
    temperature: float = 0.0
    rng: Optional[jax.Array] = None


@dataclass
class _Slot:
    """Host-side bookkeeping for one occupied pool slot."""

    req_id: int
    real_len: int
    n_new: int
    n_emitted: int  # tokens produced so far (tok0 counts)
    tokens: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)
    comm_bytes: float = 0.0
    pages: list = field(default_factory=list)  # owned page refs (paged layout)
    t_start: float = 0.0  # effective request start (submit/arrival clock)
    t_first: float = 0.0  # first token available (TTFT = t_first - t_start)


class ContinuousBatchingScheduler:
    """Admit → step → retire loop over a fixed slot pool.

    Args:
      engine: a FedAttnEngine (its compiled prefill, bucket policy,
        layers_mode and — when present — serving mesh are reused as-is).
      max_slots: pool rows = maximum concurrently-decoding requests.
      capacity: KV pages per slot. Every admitted request needs
        ``bucketed_prefill_len <= capacity`` and ``L + n_new <= capacity``.
        Under a mesh, capacity must divide by the 'model'-axis size (it is
        the sharded dim).
      steps_per_admit: decode sub-steps fused into one executable call
        (lax.scan inside the jit). Higher amortizes per-step dispatch;
        admission latency grows by the same factor. Finished slots coast
        (their surplus tokens are discarded; under the paged layout the
        surplus KV writes hit page-table sentinels and drop, under the
        dense layout they land in the slot's own row which the next
        occupant's prefill overwrites).
      kv_layout: ``'paged'`` (default) stores attention KV in a shared
        ``(num_pages, page_size)`` physical pool addressed through per-slot
        page tables; ``'dense'`` keeps the original per-slot
        ``(max_slots, capacity)`` rows. Token/logprob parity between the
        two is exact (pinned in tests/test_paged_serving.py).
      page_size: tokens per physical page (paged layout only). The working
        capacity is rounded up to a whole number of pages; ``capacity``
        itself stays the user-facing admission bound.
      num_pages: physical pages in the pool. Default
        ``max_slots * ceil(capacity / page_size)`` (same bytes as the dense
        layout, rounded up to the mesh shard count) — smaller pools
        oversubscribe: admission simply waits for pages, so short requests
        pack many more residents into the same memory.
      prefix_cache: opt-in (paged + attention-only stacks): admitted
        prompts publish their page runs; later admissions sharing a cached
        prefix map those pages copy-free and prefill only the suffix.
      spec_k: speculative draft length. ``0`` (default) is ordinary
        one-token-per-tick pooled decode; ``k > 0`` drafts ``k`` candidate
        tokens per slot per tick and verifies them in ONE multi-token
        forward, advancing each slot's frontier by its accept length + 1
        (token/logprob parity with ``spec_k=0`` is exact). Attention-only
        stacks; requires ``steps_per_admit == 1`` (each tick drafts on the
        host between verifies — and the verify already advances up to
        ``k+1`` tokens per dispatch, subsuming what step fusion buys).
      drafter: ``'ngram'`` (default — :class:`repro.serving.spec.
        NGramDrafter`) or any object implementing the drafter protocol
        (``begin``/``draft``/``update``, see :mod:`repro.serving.spec`).
      kv_quant: ``'int8'`` / ``'fp8'`` stores the paged pool as quantized
        codes with per-page-per-head scale leaves (serving/quant.py);
        ``None`` inherits the engine's setting, ``'none'`` forces off.
        Requires the paged layout; attention-only stacks (recurrent state
        has no per-position KV — init_paged_cache raises). Greedy tokens
        stay parity-exact on the pinned traces; scales are traced data, so
        the zero-recompile churn contract is unchanged.
    """

    def __init__(
        self,
        engine,
        *,
        max_slots: int = 8,
        capacity: int = 256,
        steps_per_admit: int = 1,
        kv_layout: str = "paged",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = False,
        spec_k: int = 0,
        drafter=None,
        kv_quant: Optional[str] = None,
    ):
        if max_slots < 1 or capacity < 2 or steps_per_admit < 1:
            raise ValueError("max_slots >= 1, capacity >= 2, steps_per_admit >= 1")
        if kv_layout not in ("paged", "dense"):
            raise ValueError("kv_layout must be 'paged' or 'dense'")
        if kv_quant is None:
            kv_quant = getattr(engine, "kv_quant", None)
        elif kv_quant == "none":
            kv_quant = None
        if kv_quant is not None and kv_layout != "paged":
            raise ValueError(
                "kv_quant requires kv_layout='paged': the dense slot rows "
                "have no per-page scale leaves (serving/quant.py)"
            )
        self.kv_quant = kv_quant
        if page_size < 1:
            raise ValueError("page_size >= 1")
        if spec_k < 0:
            raise ValueError("spec_k >= 0")
        if spec_k > 0:
            if not all(s.kind == "attn" for s in engine.config.layer_specs()):
                raise NotImplementedError(
                    "speculative decoding (spec_k > 0) requires an "
                    "attention-only stack: verify-then-rollback rejects a "
                    "draft by invalidating its KV rows, but recurrent "
                    "(SSM/hybrid) layers fold every token into a carried "
                    "state with no per-position KV to invalidate — "
                    "rolling back to the accepted prefix would need a "
                    "recurrent-state checkpoint per draft position. Run "
                    "SSM/hybrid pools with spec_k=0"
                )
            if steps_per_admit != 1:
                raise ValueError(
                    "spec_k > 0 requires steps_per_admit == 1: the drafter "
                    "runs on the host between verify ticks, and one verify "
                    "already advances up to spec_k+1 tokens per dispatch"
                )
        self.spec_k = spec_k
        self._drafter = resolve_drafter(drafter) if spec_k > 0 else None
        self.engine = engine
        self.max_slots = max_slots
        self.capacity = capacity
        self.steps_per_admit = steps_per_admit
        self.page_size = page_size
        self._paged = kv_layout == "paged"
        self._plan = engine._plan if engine.layers_mode == "scan" else None
        self._spmd = getattr(engine, "spmd", None)
        n_shards = (
            self._spmd.mesh.shape[self._spmd.cache_axes[0]]
            if self._spmd is not None else 1
        )

        if self._paged:
            # Device arrays and executables are keyed on the page-padded
            # capacity; ``self.capacity`` keeps the user-facing bound.
            self._cap = paging.padded_capacity(capacity, page_size)
            self._pp = paging.pages_for(self._cap, page_size)  # table width
            if num_pages is None:
                num_pages = max_slots * self._pp
                num_pages += (-num_pages) % n_shards
            elif num_pages < 1:
                raise ValueError("num_pages >= 1")
            self.num_pages = num_pages
            self._alloc = paging.PageAllocator(num_pages)
            self._prefix = None
            if prefix_cache:
                if not all(s.kind == "attn" for s in engine.config.layer_specs()):
                    raise ValueError(
                        "prefix_cache requires an attention-only stack: "
                        "recurrent (SSM/RWKV) layers carry per-slot state "
                        "that cached KV pages cannot reconstruct"
                    )
                self._prefix = paging.PrefixCache(self._alloc, page_size)
            # 'attnmass' with a real exchange ratio needs decode-time
            # stats: size the per-slot accumulated-mass leaf to the padded
            # capacity so the decode step can feed + consume it as data
            fed = engine.fed
            self._mass_width = (
                self._cap
                if fed.kv_selection == "attnmass"
                and fed.kv_exchange_ratio < 1.0
                else None
            )
            self.cache = T.init_paged_cache(
                engine.config, max_slots, num_pages, page_size,
                plan=self._plan, kv_quant=self.kv_quant,
                mass_width=self._mass_width,
            )
        else:
            if prefix_cache:
                raise ValueError("prefix_cache requires kv_layout='paged'")
            self._mass_width = None
            self._cap = capacity
            self._pp = 0
            self.num_pages = 0
            self._alloc = None
            self._prefix = None
            self.cache = engine.model.init_cache(
                max_slots, capacity, plan=self._plan
            )

        self._cache_shardings = None
        if self._spmd is not None:
            if self._paged:
                if self.num_pages % n_shards:
                    raise ValueError(
                        f"num_pages {self.num_pages} must divide over the "
                        f"{n_shards} page shards of the serving mesh"
                    )
            elif capacity % n_shards:
                raise ValueError(
                    f"capacity {capacity} must divide over the {n_shards} "
                    "cache shards of the serving mesh"
                )
            if not all(s.kind == "attn" for s in engine.config.layer_specs()):
                raise NotImplementedError(
                    "SPMD pooled decode shards the KV pool's capacity dim; "
                    "recurrent (SSM/hybrid) slot state follows the "
                    "validity/segment contract (models/ssm) but spmd_ssm's "
                    "inter-shard state hand-off does not yet compose with "
                    "the capacity-sharded slot pool — run SSM/hybrid pools "
                    "without a serving mesh"
                )
            pspecs = T.cache_pspecs(self.cache, self._spmd.cache_axes)
            self._cache_shardings = jax.tree.map(
                lambda sp: jax.sharding.NamedSharding(self._spmd.mesh, sp),
                pspecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
            self.cache = jax.device_put(self.cache, self._cache_shardings)

        S, C = max_slots, self._cap
        self._slots: list[Optional[_Slot]] = [None] * S
        # per-slot page tables (paged layout): traced DATA, entry
        # ``num_pages`` is the hole sentinel (writes drop, reads mask)
        self._pages_tbl = (
            np.full((S, self._pp), self.num_pages, np.int32)
            if self._paged else None
        )
        self.stats = {
            "full_prefills": 0,
            "suffix_prefills": 0,
            "prefill_tokens": 0,
            "peak_resident": 0,
            "peak_resident_tokens": 0,
            # speculative counters (stay 0 for spec_k=0 pools):
            # acceptance rate = spec_accepted / spec_drafted
            "spec_drafted": 0,
            "spec_accepted": 0,
            "verify_ticks": 0,
        }
        # per-request latency samples (seconds), appended at first token /
        # retirement — see latency_stats()
        self._lat = {"ttft": [], "tpot": []}
        self._submit_t: dict[int, float] = {}
        self._queue: deque = deque()  # (req_id, Request, arrival_time|None)
        self._results: dict[int, GenerationResult] = {}
        self._next_id = 0

        # per-slot traced step inputs (host mirrors, pushed every tick)
        self._tok = np.zeros(S, np.int32)  # last emitted token
        self._write_pos = np.zeros(S, np.int32)  # its KV slot = its position
        self._fold = np.zeros(S, np.int32)  # rng fold step of the NEXT token
        self._qseg = np.full(S, PAD_SEGMENT, np.int32)
        # PAD_SEGMENT ⇒ page invisible (inactive slot)
        self._kvseg = np.full((S, C), PAD_SEGMENT, np.int32)
        self._temps = np.full(S, 1.0, np.float32)
        self._sampled = np.zeros(S, bool)
        kd = jax.random.key_data(jax.random.key(0))
        self._key_shape, self._key_dtype = kd.shape, kd.dtype
        self._key_data = np.zeros((S,) + kd.shape, kd.dtype)

        self._step_fns: dict = {}
        self._verify = None
        self._draft_state: list = [None] * S
        self._write_fn = None
        self._admit_fn = None
        # executable budgets (repro.analysis.trace_guard): ONE resident
        # decode step / verify step / slot scatter / admit sampler per
        # pool — THE zero-recompile churn contract, enforceable via
        # trace_guard.enforce
        self._trace_guards = {
            "decode_step": TraceGuard("scheduler.decode_step", budget=1),
            "verify_step": TraceGuard("scheduler.verify_step", budget=1),
            "slot_write": TraceGuard("scheduler.slot_write", budget=1),
            "admit_finish": TraceGuard("scheduler.admit_finish", budget=1),
        }
        # admission-rate state, rebuilt only when the slot set changes (the
        # per-tick arrays tok/write_pos/fold are tiny; these are the wide
        # ones + the ones that cost dispatches to rebuild)
        self._slot_args = None
        # the admission prefill caches can be allocated once per
        # admission-batch bucket and reused only where the prefill does not
        # donate them (the donation policy); otherwise rebuilt per admit
        self._prefill_caches: Optional[dict] = (
            None if _donation_for_backend((1,)) else {}
        )

    def _spmd_scope(self):
        """runtime.spmd context for tracing/running pooled executables —
        the attention layers route through distributed/spmd_attention
        exactly while this is active."""
        if self._spmd is None:
            return contextlib.nullcontext()
        from repro.distributed import runtime

        s = self._spmd
        return runtime.spmd(
            s.mesh, batch_axes=s.batch_axes, seq_axis=s.seq_axis,
            cache_axes=s.cache_axes,
        )

    def _constrain_cache(self, cache):
        """Pin the pool's sharding inside jitted closures so executions
        under the mesh always hand back an identically-sharded pool (no
        sharding drift → no silent re-specialization across ticks)."""
        if self._cache_shardings is None:
            return cache
        return jax.tree.map(
            jax.lax.with_sharding_constraint, cache, self._cache_shardings
        )

    # -- introspection ----------------------------------------------------------

    @property
    def compile_counts(self) -> dict:
        """Executable counts — the recompile metric. ``decode_step`` must
        stay at 1 across any trace (per (pool shape, steps_per_admit));
        ``verify_step`` likewise for speculative pools (0 when spec_k=0)."""
        return {
            "prefill": self.engine.compile_counts["prefill"],
            "decode_step": self._trace_guards["decode_step"].count,
            "verify_step": self._trace_guards["verify_step"].count,
            "slot_write": self._trace_guards["slot_write"].count,
        }

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    def done(self) -> bool:
        return not self._queue and self.n_active == 0

    # -- submission -------------------------------------------------------------

    def submit(self, request: Request, *, arrival_time: Optional[float] = None) -> int:
        """Queue a request; returns its id (key into ``results``).
        ``arrival_time`` (time.perf_counter clock) defers admission —
        ``run`` uses it to replay recorded arrival traces."""
        toks = jnp.asarray(request.tokens)
        if toks.ndim == 2:
            if toks.shape[0] != 1:
                raise ValueError("scheduler requests are single-sequence (B=1)")
            toks = toks[0]
        L = int(toks.shape[0])
        Lp = self.engine._bucket_len(L)
        if max(Lp, L + request.n_new) > self.capacity:
            raise ValueError(
                f"request needs {max(Lp, L + request.n_new)} KV pages "
                f"(L={L}, bucketed {Lp}, n_new={request.n_new}) but slots "
                f"hold {self.capacity}"
            )
        req = dataclasses.replace(request, tokens=toks)
        rid = self._next_id
        self._next_id += 1
        self._submit_t[rid] = time.perf_counter()
        self._queue.append((rid, req, arrival_time))
        return rid

    @property
    def results(self) -> dict[int, GenerationResult]:
        """Completed results by request id. A resident submit/step loop
        should claim them with :meth:`pop_result` — results left here are
        retained forever (``run`` pops its own)."""
        return self._results

    def pop_result(self, rid: int) -> Optional[GenerationResult]:
        """Claim (and free) a completed request's result, or None if the
        request is still queued/in flight."""
        return self._results.pop(rid, None)

    @staticmethod
    def capacity_for(engine, requests) -> int:
        """Smallest slot capacity serving every request: the bucketed
        prefill length and the prompt+generation span must both fit. Kept
        exact (no pow2 rounding) — every page of width costs attention
        FLOPs in every slot at every step, and pool executables are keyed
        on the capacity anyway. Under a serving mesh the result is rounded
        up to a multiple of the shard count (capacity is the sharded dim)."""
        need = 2
        for r in requests:
            L = int(jnp.asarray(r.tokens).reshape(-1).shape[0])
            need = max(need, engine._bucket_len(L), L + r.n_new)
        spmd = getattr(engine, "spmd", None)
        if spmd is not None:
            n = spmd.mesh.shape[spmd.cache_axes[0]]
            need += (-need) % n
        return need

    # -- admission --------------------------------------------------------------

    def _admit_batch_size(self, B: int, Lp: int, n_rounds, tag=True) -> int:
        """pow2-pad the admission batch, preferring the smallest ALREADY
        COMPILED (B', Lp) prefill with Bp <= B' <= 2·Bp: re-using a
        slightly larger executable costs at most one doubling of padded
        rows, while a fresh compile costs seconds — so a pool that once
        admitted a 4-wide group keeps serving later 2- or 3-wide groups
        with zero new executables (the coalescing contract pinned in
        test_scheduler.py). The 2x cap matters: without it a lone
        re-admission would ride the widest executable ever compiled and
        burn B_max/1 padded prefill FLOPs per admit (observed as a ~30%
        pooled-throughput hit on the 2-vCPU box)."""
        Bp = _next_pow2(B)
        compiled = sorted(
            k[0] for k in self.engine._prefill_fns
            if k[1:] == (Lp, self._cap, n_rounds, False, tag)
            and Bp <= k[0] <= 2 * Bp
        )
        return compiled[0] if compiled else Bp

    def _prefix_key(self, req, ctx):
        """Length-indexed digest of everything that determines a prompt's
        KV bytes — token ids, partition segment labels, and the sparse-
        exchange contribution masks. Two prompts share cached pages only
        when all three agree over the shared span."""
        toks = np.asarray(req.tokens)
        segs = np.asarray(ctx.segments)
        contrib = (
            None if ctx.contributed is None else np.asarray(ctx.contributed)
        )

        def key_of(d: int) -> bytes:
            h = hashlib.blake2b(digest_size=16)
            h.update(np.int64(d).tobytes())
            h.update(toks[:d].tobytes())
            h.update(segs[:d].tobytes())
            if contrib is not None:
                h.update(np.ascontiguousarray(contrib[:, :d]).tobytes())
            return h.digest()

        return key_of

    def _alloc_pages(self, n: int):
        """All-or-nothing page allocation, evicting prefix-cache LRU
        entries under pressure (cold cached prefixes yield their pages to
        live admissions). None ⇒ the pool genuinely cannot hold ``n`` more
        pages right now."""
        if n == 0:
            return []
        out = self._alloc.alloc(n)
        while out is None and self._prefix is not None and self._prefix.evict_lru():
            out = self._alloc.alloc(n)
        return out

    def _prepare_admission(self, rid: int, req: Request):
        """Build the request's decode context and — under the paged
        layout — its page plan: prefix-cache lookup, refcounted shares of
        full prefix pages, a copy-on-write page when the prefix ends
        mid-page, fresh pages for the rest of the prompt+generation span.

        Returns an admission dict, or None when the pool cannot hold the
        request right now (every ref taken here is rolled back; the caller
        leaves the request at the head of the queue — admission is FIFO,
        later smaller requests do not jump a starved large one)."""
        eng = self.engine
        L = int(req.tokens.shape[0])
        ctx = eng.build_context(L, partition=req.partition, rng=req.rng)
        adm = {
            "rid": rid, "req": req, "ctx": ctx, "L": L, "d": 0,
            "pages": [], "dst": None, "src": None, "table": None,
            "key_of": None, "t0": None,
        }
        if not self._paged:
            return adm
        ps = self.page_size
        N = self.num_pages
        # speculative pools allocate worst-case draft headroom up front
        # (capped at the table width — writes past the working capacity
        # drop at the scatter); surplus pages come back at retire
        n_total = min(
            paging.pages_for_request(L, req.n_new, ps, spec_k=self.spec_k),
            self._pp,
        )
        d, run = 0, ()
        if self._prefix is not None:
            adm["key_of"] = self._prefix_key(req, ctx)
            hit = self._prefix.lookup(adm["key_of"], L)
            if hit is not None:
                d, run = hit
        n_shared = paging.pages_for(d, ps)
        partial = d > 0 and paging.page_split(d, ps)[1] != 0
        owned: list = []
        table = np.full(self._pp, N, np.int32)
        dst = np.full(self._pp, N, np.int32)
        src = np.full(self._pp, N, np.int32)
        # shared prefix pages: the slot takes a ref on each; reads go
        # straight to the shared page (src + decode table) and the
        # admission scatter skips it (dst sentinel) — shared bytes stay
        # immutable while any reference lives
        for j, p in enumerate(run[:n_shared]):
            self._alloc.incref(p)
            owned.append(p)
            src[j] = table[j] = p
        if partial:
            # copy-on-write: the prefix ends mid-page, so the suffix write
            # must not touch the shared copy. The prefill gathers through
            # the shared page (src) and the scatter rewrites a fresh
            # private page (dst/table) with identical prefix bytes + the
            # new suffix. The slot keeps its ref on the shared original
            # until retirement so eviction cannot recycle it pre-gather.
            copy = self._alloc_pages(1)
            if copy is None:
                for p in owned:
                    self._alloc.free(p)
                return None
            j = n_shared - 1
            dst[j] = table[j] = copy[0]
            owned.append(copy[0])
        fresh = self._alloc_pages(n_total - n_shared)
        if fresh is None:
            for p in owned:
                self._alloc.free(p)
            return None
        for j, p in enumerate(fresh):
            dst[n_shared + j] = table[n_shared + j] = p
            owned.append(p)
        adm.update(d=d, pages=owned, dst=dst, src=src, table=table)
        return adm

    def pool_stats(self) -> dict:
        """Memory + prefix-cache effectiveness counters for the pool:
        ``bytes_per_resident_token`` is the whole slot pool (attention KV
        + any recurrent state) divided by the tokens actually resident —
        the paged layout's headline win over dense rows (benchmarks/
        serving_throughput.py, ``serving_paged_prefix`` record)."""
        resident = sum(
            occ.real_len + occ.n_emitted
            for occ in self._slots if occ is not None
        )
        pool_bytes = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(self.cache)
        )
        out = {
            "kv_layout": "paged" if self._paged else "dense",
            "pool_bytes": int(pool_bytes),
            "resident_tokens": int(resident),
            "bytes_per_resident_token": float(pool_bytes) / max(1, resident),
            "peak_bytes_per_resident_token": (
                float(pool_bytes)
                / max(1, self.stats["peak_resident_tokens"])
            ),
            **self.stats,
        }
        if self._paged:
            out["num_pages"] = self.num_pages
            out["page_size"] = self.page_size
            out["used_pages"] = self._alloc.used_pages
            out["free_pages"] = self._alloc.free_pages
        if self._prefix is not None:
            out["prefix_hits"] = self._prefix.hits
            out["prefix_misses"] = self._prefix.misses
            out["prefix_evictions"] = self._prefix.evictions
            out["prefix_tokens_reused"] = self._prefix.tokens_reused
            out["prefix_entries"] = len(self._prefix)
        if self.spec_k > 0:
            out["spec_k"] = self.spec_k
            out["spec_acceptance_rate"] = (
                self.stats["spec_accepted"] / max(1, self.stats["spec_drafted"])
            )
        out.update(self.latency_stats())
        return out

    def latency_stats(self, *, reset: bool = False) -> dict:
        """Per-request latency percentiles (seconds) over every request
        retired so far: ``ttft`` — time to first token, from the later of
        submission and scheduled arrival to the admission prefill's output;
        ``tpot`` — time per output token after the first, retirement minus
        first-token time over ``n_new - 1`` (only requests with
        ``n_new > 1`` contribute). Per-request decode speed is
        ``1 / tpot`` — the metric speculative decoding moves, reported by
        ``launch/serve.py --stream`` next to aggregate tok/s.
        ``reset=True`` drains the samples (benchmarks measure per-pass)."""
        out: dict = {}
        for name, xs in self._lat.items():
            out[f"{name}_n"] = len(xs)
            if xs:
                out[f"{name}_p50"] = float(np.percentile(xs, 50))
                out[f"{name}_p95"] = float(np.percentile(xs, 95))
        if reset:
            for xs in self._lat.values():
                xs.clear()
        return out

    def _admit_group(self, slots: list[int], adms: list, Lp: int,
                     *, suffix: bool = False) -> None:
        """Admit same-bucket requests with ONE B>1 bucketed prefill.

        The admission batch is pow2-padded (padding rows replicate request
        0 — their compute is discarded and their slot index is out of range,
        so the slot scatter drops them), keeping the executable set bounded:
        one per (B-bucket, L-bucket), with upward reuse of already-compiled
        wider batches (:meth:`_admit_batch_size`). Per-request state flows
        as per-row vectors (real_len, segments, kv segments, contribution
        masks, sampling knobs) — the batched-vector contract of
        kernels.core.

        ``suffix=True`` (prefix-cache hits): ``Lp`` buckets the SUFFIX
        lengths and the group runs ``engine._suffix_prefill_fn`` — cached
        prefix KV is gathered from the pool through each row's source page
        table, only the suffix tokens run through the layers at traced
        per-row write frontiers. Either way the resulting transient goes
        through the same slot scatter (paged: routed by per-row
        destination page tables, where sentinel entries skip shared
        immutable prefix pages)."""
        eng = self.engine
        B = len(adms)
        C = self._cap

        tokens = np.zeros((B, Lp), np.int32)
        real_len = np.ones(B, np.int32)
        write_lo = np.zeros(B, np.int32)
        q_seg = np.full((B, Lp), PAD_SEGMENT, np.int32)
        kv_seg = np.zeros((B, C), np.int32)
        temps = np.ones(B, np.float32)
        sampled = np.zeros(B, bool)
        key_data = np.zeros((B,) + self._key_shape, self._key_dtype)
        contrib_rows = []
        for i, a in enumerate(adms):
            req, ctx, L, d = a["req"], a["ctx"], a["L"], a["d"]
            tokens[i, : L - d] = np.asarray(req.tokens)[d:]
            real_len[i] = L - d
            write_lo[i] = d
            q_seg[i, : L - d] = np.asarray(ctx.segments)[d:]
            kv_seg[i] = np.asarray(ctx.decode_kv_segments(C))
            temps[i] = max(req.temperature, 1e-6)
            sampled[i] = req.temperature > 0.0 and req.rng is not None
            key = req.rng if req.rng is not None else jax.random.key(0)
            key_data[i] = np.asarray(jax.random.key_data(key))
            if ctx.contributed is not None:
                rounds = ctx.contributed.shape[0]
                row = np.zeros((rounds, C), bool)
                row[:, : ctx.contributed.shape[1]] = np.asarray(ctx.contributed)
                contrib_rows.append(row)
        n_rounds = contrib_rows[0].shape[0] if contrib_rows else None

        Bp = self._admit_batch_size(
            B, Lp, n_rounds, "suffix" if suffix else True
        )
        pad = lambda a: np.concatenate(
            [a, np.broadcast_to(a[:1], (Bp - B,) + a.shape[1:])]
        ) if Bp > B else a  # padding rows replicate request 0
        contributed = None
        if contrib_rows:
            contributed = jnp.asarray(pad(np.stack(contrib_rows)))
        if suffix:
            # gather tables: padding rows stay all-sentinel (clamped
            # garbage gather; their compute is discarded anyway)
            src = np.full((Bp, self._pp), self.num_pages, np.int32)
            src[:B] = np.stack([a["src"] for a in adms])
            fn = eng._suffix_prefill_fn(Bp, Lp, C, n_rounds)
            last, one = fn(
                eng._run_params(), self.cache, jnp.asarray(src),
                jnp.asarray(pad(tokens)), jnp.asarray(pad(real_len)),
                jnp.asarray(pad(write_lo)), jnp.asarray(pad(q_seg)),
                jnp.arange(C, dtype=jnp.int32), jnp.asarray(pad(kv_seg)),
                contributed,
            )
        else:
            one = None
            if self._prefill_caches is not None:
                one = self._prefill_caches.get(Bp)
            if one is None:
                one = eng.model.init_cache(Bp, C, plan=self._plan)
                if self._prefill_caches is not None:
                    self._prefill_caches[Bp] = one
            fn = eng._prefill_fn(Bp, Lp, C, n_rounds, False, per_row=True)
            last, one = fn(
                eng._run_params(), one, jnp.asarray(pad(tokens)),
                jnp.asarray(pad(real_len)), jnp.arange(Lp, dtype=jnp.int32),
                jnp.asarray(pad(q_seg)), jnp.arange(C, dtype=jnp.int32),
                jnp.asarray(pad(kv_seg)), contributed, None,
            )
        tok0, lp0 = self._admit_finish_fn()(
            last, jnp.asarray(pad(temps)), jnp.asarray(pad(key_data)),
            jnp.asarray(pad(sampled)),
        )
        # scatter the real rows into their slots (padding rows get an
        # out-of-range index and drop via scatter OOB semantics)
        slot_idx = np.full(Bp, self.max_slots, np.int32)
        slot_idx[:B] = slots
        if self._paged:
            dst = np.full((Bp, self._pp), self.num_pages, np.int32)
            dst[:B] = np.stack([a["dst"] for a in adms])
            self.cache = self._slot_write_fn()(
                self.cache, one, jnp.asarray(slot_idx), jnp.asarray(dst)
            )
        else:
            self.cache = self._slot_write_fn()(
                self.cache, one, jnp.asarray(slot_idx)
            )

        tok0 = np.asarray(tok0)
        lp0 = np.asarray(lp0)
        t_now = time.perf_counter()  # tok0 materialized ⇒ first token exists
        for i, a in enumerate(adms):
            slot, ctx, req, rid = slots[i], a["ctx"], a["req"], a["rid"]
            L, d = a["L"], a["d"]
            self._tok[slot] = int(tok0[i])
            self._write_pos[slot] = L  # tok0's KV goes to position L next tick
            self._fold[slot] = 1  # token m samples with fold_in(rng, m)
            self._qseg[slot] = ctx.partition.publisher(ctx.config.publisher_index)
            self._kvseg[slot] = kv_seg[i]
            self._temps[slot] = temps[i]
            self._sampled[slot] = sampled[i]
            self._key_data[slot] = key_data[i]
            if self._paged:
                self._pages_tbl[slot] = a["table"]
            t0 = a["t0"] if a["t0"] is not None else t_now
            self._lat["ttft"].append(t_now - t0)
            self._slots[slot] = _Slot(
                req_id=rid,
                real_len=L,
                n_new=req.n_new,
                n_emitted=1,
                tokens=[int(tok0[i])],
                logprobs=[float(lp0[i])],
                comm_bytes=ctx.comm_bytes_per_participant(
                    eng.config.n_kv_heads, eng.config.head_dim
                ),
                pages=a["pages"],
                t_start=t0,
                t_first=t_now,
            )
            if self.spec_k > 0:
                # draft state sees the prompt plus the first emitted token
                self._draft_state[slot] = self._drafter.begin(
                    list(np.asarray(req.tokens)) + [int(tok0[i])]
                )
            if suffix:
                self.stats["suffix_prefills"] += 1
                self.stats["prefill_tokens"] += L - d
            else:
                self.stats["full_prefills"] += 1
                self.stats["prefill_tokens"] += L
            if self._prefix is not None and a["key_of"] is not None:
                # publish this prompt's page run (entry refs protect the
                # pages past this slot's retirement) — BEFORE any
                # n_new==1 instant retirement frees the slot's own refs
                self._prefix.insert(
                    a["key_of"], L,
                    [int(p) for p in
                     a["table"][: paging.pages_for(L, self.page_size)]],
                )
            if req.n_new == 1:
                self._retire(slot)
        self._slot_args = None  # slot set changed; re-upload wide arrays

    def _retire(self, slot: int) -> None:
        occ = self._slots[slot]
        self._results[occ.req_id] = GenerationResult(
            tokens=np.asarray(occ.tokens, np.int64)[None, : occ.n_new],
            logprobs=np.asarray(occ.logprobs, np.float64)[None, : occ.n_new],
            prefill_comm_bytes=occ.comm_bytes,
        )
        if occ.n_new > 1:
            self._lat["tpot"].append(
                (time.perf_counter() - occ.t_first) / (occ.n_new - 1)
            )
        self._slots[slot] = None
        self._draft_state[slot] = None
        # hide the freed pages from every query until the next occupant's
        # prefill rewrites the row
        self._kvseg[slot] = PAD_SEGMENT
        self._qseg[slot] = PAD_SEGMENT
        self._sampled[slot] = False
        if self._paged:
            # drop the slot's page refs (pages shared with the prefix
            # cache or other slots stay alive) and sentinel the table so
            # a coasting write from this slot's final fused call drops
            for p in occ.pages:
                self._alloc.free(p)
            self._pages_tbl[slot] = self.num_pages
        self._slot_args = None

    def _admit_finish_fn(self):
        """Jitted fused first-token sampler over a whole admission batch:
        one dispatch instead of a per-request argmax/fold_in/categorical/
        log-softmax chain — row ``i``'s semantics are exactly
        engine._sample(last[i], temp, rng, step=0) plus _token_logprob."""
        if self._admit_fn is not None:
            return self._admit_fn

        def finish(last, temps, key_data, sampled):
            keys = jax.random.wrap_key_data(key_data)
            greedy = jnp.argmax(last, axis=-1)
            folded = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
            cat = jax.vmap(
                lambda k, l, t: jax.random.categorical(
                    k, l.astype(jnp.float32) / t
                )
            )(folded, last, temps)
            tok0 = jnp.where(sampled, cat, greedy)
            return tok0, _token_logprob(last, tok0)

        self._trace_guards["admit_finish"].charge(())
        self._admit_fn = jax.jit(finish)
        return self._admit_fn

    # -- the resident decode step -----------------------------------------------

    def _slot_write_fn(self):
        """Jitted whole-row scatter of an admission batch's caches into the
        pool (slot indices traced — one executable regardless of which
        slots admit; out-of-range indices, used by pow2 padding rows, drop).
        Under a mesh the written pool keeps the capacity sharding."""
        if self._write_fn is not None:
            return self._write_fn

        scan_form = isinstance(self.cache, dict)

        if self._paged:
            # paged layout: attention KV routes through per-row destination
            # page tables (sentinel entries — padding rows, shared
            # immutable prefix pages — drop at the scatter); recurrent
            # state still replaces whole slot rows
            def write_paged(pool, batch, slots, dst_pages):
                return self._constrain_cache(
                    T.paged_slot_write(pool, batch, dst_pages, slots)
                )

            self._trace_guards["slot_write"].charge(())
            self._write_fn = jax.jit(
                write_paged, donate_argnums=_donation_for_backend((0,))
            )
            return self._write_fn

        def write(pool, batch, slots):
            if scan_form:
                # stacked leaves: (n_periods, B, ...) — batch axis 1
                stacked = jax.tree.map(
                    lambda pl, ol: pl.at[:, slots].set(ol.astype(pl.dtype)),
                    pool["stacked"], batch["stacked"],
                )
                remainder = jax.tree.map(
                    lambda pl, ol: pl.at[slots].set(ol.astype(pl.dtype)),
                    pool["remainder"], batch["remainder"],
                )
                out = {"stacked": stacked, "remainder": remainder}
            else:
                out = jax.tree.map(
                    lambda pl, ol: pl.at[slots].set(ol.astype(pl.dtype)),
                    pool, batch,
                )
            return self._constrain_cache(out)

        self._trace_guards["slot_write"].charge(())
        self._write_fn = jax.jit(write, donate_argnums=_donation_for_backend((0,)))
        return self._write_fn

    def _decode_proto(self):
        """The pooled decode steps' prototype context. ``_proto_ctx`` bakes
        ``kv_exchange_ratio=1.0`` (full exchange — no per-layer rng in the
        jitted step); when the pool carries the 'attnmass' accumulator the
        REAL ratio must survive into the decode trace, because it gates
        the decode-time sparse-exchange mask derivation
        (models/attention: decode_exchange_mask) — a deterministic
        top-k, still rng-free."""
        proto = self.engine._proto_ctx(self._cap)
        if self._mass_width is not None:
            proto = dataclasses.replace(
                proto,
                config=proto.config.replace(
                    kv_exchange_ratio=self.engine.fed.kv_exchange_ratio
                ),
            )
        return proto

    def _step_fn(self, n_steps: int):
        """Build (or fetch) THE decode executable: ``n_steps`` fused
        sub-steps over all slots. Static key = (pool shape, n_steps) only;
        per-slot frontiers/segments/sampling state are traced, so admission
        and retirement never trigger a recompile — with or without a mesh
        (the SPMD variant differs only in where the attention math runs:
        the trace happens under the runtime.spmd scope, routing it through
        the flash-decoding shard_map)."""
        key = n_steps
        fn = self._step_fns.get(key)
        if fn is not None:
            return fn

        eng = self.engine
        model, backend = eng.model, eng.backend
        mode, plan = eng.layers_mode, eng._plan
        proto = self._decode_proto()
        kv_pos = jnp.arange(self._cap, dtype=jnp.int32)

        def run(params, cache, tok, write_pos, fold, q_seg, kv_seg,
                temps, sampled, key_data, pages=None):
            keys = jax.random.wrap_key_data(key_data)

            def body(carry, _):
                cache, tok, wp, fold = carry
                dctx = dataclasses.replace(
                    proto,
                    positions=wp[:, None], segments=q_seg[:, None],
                    kv_positions=kv_pos, kv_segments=kv_seg,
                    contributed=None,
                )
                logits, cache = model.decode_step(
                    params, cache, tok[:, None], wp, proto,
                    backend=backend, dctx=dctx, mode=mode, plan=plan,
                    pages=pages,
                )
                last = logits[:, -1]
                greedy = jnp.argmax(last, axis=-1)
                folded = jax.vmap(jax.random.fold_in)(keys, fold)
                cat = jax.vmap(
                    lambda k, l, t: jax.random.categorical(
                        k, l.astype(jnp.float32) / t
                    )
                )(folded, last, temps)
                nxt = jnp.where(sampled, cat, greedy)
                lp = _token_logprob(last, nxt)
                return (cache, nxt, wp + 1, fold + 1), (nxt, lp)

            (cache, _, _, _), (toks, lps) = jax.lax.scan(
                body, (cache, tok, write_pos, fold), None, length=n_steps
            )
            return toks, lps, self._constrain_cache(cache)  # (n_steps, S)

        self._trace_guards["decode_step"].charge(key)
        fn = jax.jit(run, donate_argnums=_donation_for_backend((1,)))
        self._step_fns[key] = fn
        return fn

    def _verify_fn(self):
        """Build (or fetch) THE speculative verify executable: one
        multi-token decode forward scoring all ``spec_k + 1`` query
        positions of every slot at once. Static key = (pool shape, spec_k)
        only — the draft tokens, per-slot frontiers and accept state are
        all traced, so slot churn and ragged advances never recompile
        (budget: ``verify_step = 1`` per pool, same contract as
        ``decode_step``). Each slot's query block ``[last_tok, d_1..d_k]``
        rides per-row 2-D positions ``frontier..frontier+k`` and broadcast
        publisher segments — the bucketed-prefill visibility contract of
        kernels.core, no new mask logic. KV for all k+1 rows is written
        before the attention reads (the decode-layer contract), which is
        also what makes rejected rows harmless: the next tick's write
        block starts at the accepted frontier and re-covers them before
        any later query can look that far."""
        if self._verify is not None:
            return self._verify

        eng = self.engine
        model, backend = eng.model, eng.backend
        mode, plan = eng.layers_mode, eng._plan
        proto = self._decode_proto()
        kv_pos = jnp.arange(self._cap, dtype=jnp.int32)
        offs = jnp.arange(self.spec_k + 1, dtype=jnp.int32)

        def run(params, cache, tok, draft, write_pos, fold, q_seg, kv_seg,
                temps, sampled, key_data, pages=None):
            keys = jax.random.wrap_key_data(key_data)
            inp = jnp.concatenate([tok[:, None], draft], axis=1)  # (S, k+1)
            pos = write_pos[:, None] + offs[None, :]
            dctx = dataclasses.replace(
                proto,
                positions=pos,
                segments=jnp.broadcast_to(q_seg[:, None], pos.shape),
                kv_positions=kv_pos, kv_segments=kv_seg,
                contributed=None,
            )
            logits, cache = model.decode_step(
                params, cache, inp, write_pos, proto,
                backend=backend, dctx=dctx, mode=mode, plan=plan,
                pages=pages,
            )  # (S, k+1, V) — every position's logits, not just the last
            cand, lps, accept = _verify_candidates(
                logits, draft, temps, keys, fold, sampled
            )
            return cand, lps, accept, self._constrain_cache(cache)

        self._trace_guards["verify_step"].charge(self.spec_k)
        self._verify = jax.jit(run, donate_argnums=_donation_for_backend((1,)))
        return self._verify

    # -- the scheduler tick -----------------------------------------------------

    def step(self, *, now: Optional[float] = None) -> bool:
        """One tick: admit every arrived request into free slots (one
        coalesced bucketed prefill per shape bucket), run one fused decode
        call over the pool, retire finished slots. Returns True if any
        decode work ran (False ⇒ idle: nothing active and nothing
        admissible yet)."""
        free = [s for s, occ in enumerate(self._slots) if occ is None]
        batch: list = []
        while self._queue and len(batch) < len(free):
            rid, req, at = self._queue[0]
            if at is not None and at > (now if now is not None else time.perf_counter()):
                break
            adm = self._prepare_admission(rid, req)
            if adm is None:
                # page pool exhausted (even after prefix-cache eviction) —
                # the request stays at the head of the queue until
                # retirements free pages; admission stays FIFO
                break
            self._queue.popleft()
            # latency clock: a request "starts" at the later of submission
            # and its scheduled arrival (trace replays submit up front)
            t0 = self._submit_t.pop(rid, None)
            if t0 is None:
                t0 = time.perf_counter()
            adm["t0"] = t0 if at is None else max(t0, at)
            batch.append(adm)
        if batch:
            groups: dict = {}
            for adm in batch:
                # coalesce same-bucket admissions into one B>1 prefill —
                # THE single admission path, every stack kind (per-row
                # segment vectors drive attention visibility and the
                # recurrence validity/reset masks alike). Prefix-cache
                # hits bucket by SUFFIX length into their own groups.
                Lp = self.engine._bucket_len(adm["L"] - adm["d"])
                groups.setdefault((Lp, adm["d"] > 0), []).append(adm)
            for (Lp, suffix), adms in groups.items():
                self._admit_group(
                    [free.pop(0) for _ in adms], adms, Lp, suffix=suffix
                )
            self.stats["peak_resident"] = max(
                self.stats["peak_resident"], self.n_active
            )
            self.stats["peak_resident_tokens"] = max(
                self.stats["peak_resident_tokens"],
                sum(o.real_len + o.n_emitted
                    for o in self._slots if o is not None),
            )

        if self.n_active == 0:
            return False

        if self.spec_k > 0:
            # host-side drafting: inactive rows keep zeros (their verify
            # compute is discarded behind the PAD_SEGMENT mask anyway)
            draft = np.zeros((self.max_slots, self.spec_k), np.int32)
            for s, occ in enumerate(self._slots):
                if occ is not None:
                    draft[s] = self._drafter.draft(
                        self._draft_state[s], self.spec_k
                    )

        with self._spmd_scope():
            fn = (
                self._verify_fn() if self.spec_k > 0
                else self._step_fn(self.steps_per_admit)
            )
            if self._slot_args is None:
                # wide / admission-rate inputs: re-uploaded only when the
                # slot set changed, not every tick
                self._slot_args = (
                    jnp.asarray(self._qseg), jnp.asarray(self._kvseg),
                    jnp.asarray(self._temps), jnp.asarray(self._sampled),
                    jnp.asarray(self._key_data),
                ) + (
                    (jnp.asarray(self._pages_tbl),) if self._paged else ()
                )
            q_seg, kv_seg, temps, sampled, key_data = self._slot_args[:5]
            if self.spec_k > 0:
                cand, lps, acc, self.cache = fn(
                    self.engine._run_params(), self.cache,
                    jnp.asarray(self._tok), jnp.asarray(draft),
                    jnp.asarray(self._write_pos), jnp.asarray(self._fold),
                    q_seg, kv_seg, temps, sampled, key_data,
                    *self._slot_args[5:],
                )
            else:
                toks, lps, self.cache = fn(
                    self.engine._run_params(), self.cache,
                    jnp.asarray(self._tok), jnp.asarray(self._write_pos),
                    jnp.asarray(self._fold), q_seg, kv_seg, temps, sampled,
                    key_data, *self._slot_args[5:],
                )

        if self.spec_k > 0:
            # ragged frontier advance: slot s moves by accept+1 (its
            # accepted drafts plus the correction/bonus token), its
            # neighbor by whatever IT accepted — all from ONE verify call
            cand = np.asarray(cand)  # (S, k+1)
            lps = np.asarray(lps)
            acc = np.asarray(acc)  # (S,) accept lengths in [0, k]
            self.stats["verify_ticks"] += 1
            for s, occ in enumerate(self._slots):
                if occ is None:
                    continue
                a = int(acc[s])
                take = min(a + 1, occ.n_new - occ.n_emitted)
                occ.tokens.extend(int(t) for t in cand[s, :take])
                occ.logprobs.extend(float(l) for l in lps[s, :take])
                occ.n_emitted += take
                self._tok[s] = int(cand[s, take - 1])
                self._write_pos[s] += take
                self._fold[s] += take
                self._drafter.update(self._draft_state[s], cand[s, :take])
                self.stats["spec_drafted"] += self.spec_k
                self.stats["spec_accepted"] += min(a, take)
                if occ.n_emitted >= occ.n_new:
                    self._retire(s)
            return True

        toks = np.asarray(toks)
        lps = np.asarray(lps)
        k = self.steps_per_admit
        for s, occ in enumerate(self._slots):
            if occ is None:
                continue
            take = min(k, occ.n_new - occ.n_emitted)
            occ.tokens.extend(int(t) for t in toks[:take, s])
            occ.logprobs.extend(float(l) for l in lps[:take, s])
            occ.n_emitted += take
            self._tok[s] = int(toks[-1, s])
            self._write_pos[s] += k
            self._fold[s] += k
            if occ.n_emitted >= occ.n_new:
                self._retire(s)
        return True

    # -- drive to completion ----------------------------------------------------

    def run(self, requests: Sequence[Request],
            arrival_times: Optional[Sequence[float]] = None
            ) -> list[GenerationResult]:
        """Submit ``requests`` (optionally with perf_counter arrival
        offsets measured from now) and drive the loop until all complete.
        Returns results in request order."""
        t0 = time.perf_counter()
        ids = [
            self.submit(
                r,
                arrival_time=None if arrival_times is None else t0 + arrival_times[i],
            )
            for i, r in enumerate(requests)
        ]
        while not self.done():
            if not self.step():
                # idle: nothing active — wait for the next arrival
                nxt = min(
                    (at for _, _, at in self._queue if at is not None),
                    default=None,
                )
                if nxt is not None:
                    time.sleep(max(0.0, nxt - time.perf_counter()))
        # claim our results (don't grow the dict across repeated runs)
        return [self._results.pop(i) for i in ids]
