"""FedAttn collaborative-inference engine — compiled end to end.

Implements the paper's full inference flow (§IV):

  1. **Prefill** (the non-autoregressive phase, Algorithm 1): participants'
     token segments are prefix-assembled into the global sequence; the
     model runs with the FedAttn visibility schedule, producing per-layer
     KV caches — local KVs at local layers, global KVs at sync layers
     (here: one physical cache with visibility masks, §IV-C).
  2. **Decode**: the task publisher autoregressively extends from the final
     global token, attending per layer according to the same schedule.

With ``compile=True`` (default) both phases run as cached ``jax.jit``
executables; ``compile=False`` keeps the original eager per-token /
per-layer Python loops as the reference semantics (parity is pinned in
``tests/test_engine_decode.py``).

Compiled-serving architecture
-----------------------------
* **Jitted prefill** — one fused forward seeds the whole KV cache by bulk
  decode-writes and returns only the final-position logits (the LM head
  runs on a single position, not all L). Everything that varies per call —
  tokens, positions, segments, sparse-exchange contribution masks — is a
  traced argument, so one executable serves any partition / rng / request
  in the same shape bucket.
* **Shape bucketing** — request length L and n_new are padded up to
  power-of-two buckets (``bucket='pow2'``). Padded prefill tokens carry
  segment ``-1``, the repo-wide padding sentinel (kernels pad with ``-2``):
  the FedAttn visibility mask excludes them from every real query, and the
  garbage they bulk-write into cache slots [L, Lp) sits strictly past the
  decode write frontier, so the fixed-capacity causal convention masks it
  until the slot is overwritten by a real generated token. Mixed request
  lengths therefore share one executable per bucket — steady-state serving
  does zero recompilation. The same sentinel drives the recurrent stacks
  (mamba/rwkv): a segment ``-1`` token is an IDENTITY state update (Δ·mask
  gating, decay/k masking, valid-aware conv/token-shift carries — the
  validity contract of models/ssm + kernels/core), so SSM/hybrid stacks
  bucket L exactly like attention stacks; n_new bucketing is always safe
  (extra steps happen after the kept tokens). The trade-off is the classic
  one: up to ~2x padded work at the top of a bucket (both the padded
  prefill and the discarded decode tail) in exchange for executable reuse
  — ``bucket='none'`` opts out per engine.
* **Scan-over-layers** — when the sync schedule is periodic over the layer
  body (``ScanPlan.from_schedule``), prefill and decode lower as one
  ``lax.scan`` over the repeating layer unit with stacked params and
  stacked per-period KV caches: traced HLO is O(period), not O(n_layers),
  so deep configs compile in near-constant time. ``layers_mode`` forces
  'loop'/'scan'; the default picks scan whenever the plan applies. Note
  the stacked params are a second resident copy of the weights (the
  loop-form copy backs the eager reference path) — fine at reduced scale;
  full-size serving should init directly in scan form
  (``transformer.init_stacked``) and force ``layers_mode='scan'``.
* **Executable caches** — ``_prefill_fns`` / ``_decode_fns`` are keyed on
  the bucketed shapes only (never on partition content); the real length
  enters the decode driver as a traced scalar. ``compile_counts`` exposes
  the cache sizes to benchmarks/tests as the recompile metric.

The engine also supports batched requests (same partition structure across
the batch — the SPMD-friendly regime) and greedy or temperature sampling.
This is the small-scale/real-execution counterpart of launch/serve.py's
full-size lowering.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.analysis.trace_guard import TraceGuard
from repro.core.fedattn import FedAttnContext
from repro.core.partition import Partition
from repro.configs import schedule_from_config
from repro.kernels.core import PAD_SEGMENT
from repro.models import build_model
from repro.models import layers as LY
from repro.models import transformer as T
from repro.types import FedAttnConfig, ModelConfig


def _donation_for_backend(argnums, backend: Optional[str] = None) -> tuple:
    """The repo's ONE donation policy (audited by analysis/jaxpr_audit).

    ``argnums`` name the KV cache/pool operands of a jitted serving entry
    point: on accelerator backends they are donated so the compiled step
    updates them in place (decode would otherwise double the pool's memory
    every tick); on CPU XLA ignores donation and warns, so the declared set
    is empty there.  engine.py and scheduler.py route every ``jax.jit``
    through this helper — the jaxpr audit asserts each entry point's
    declared donation matches this policy, so a silently dropped
    ``donate_argnums`` (the bug this replaced: two inline backend checks
    that new entry points forgot to copy) is caught statically."""
    be = backend if backend is not None else jax.default_backend()
    return tuple(argnums) if be != "cpu" else ()


@dataclass
class GenerationResult:
    """Output of one generation request (``generate`` / the scheduler).

    ``logprobs[b, t]`` is the **untempered** model log-probability of the
    emitted token — ``log_softmax(logits)[token]`` at temperature 1 — even
    when the token was *sampled* at ``temperature != 1``. It scores the
    emitted text under the model's own distribution (comparable across
    temperature sweeps); it is NOT the probability the sampler actually
    drew the token with. Divide logits by the temperature yourself if you
    need sampler-calibrated scores (ROADMAP: sampled-decode logprob
    semantics).

    Sampling is only active when BOTH ``temperature > 0`` AND an ``rng``
    key are passed: ``temperature > 0`` with ``rng=None`` silently decodes
    greedily (argmax), by design — a missing key must not invent
    nondeterminism. Greedy logprobs are therefore always each row's
    maximum.
    """

    tokens: np.ndarray  # (B, n_new)
    logprobs: Optional[np.ndarray] = None  # (B, n_new) — model logprob of each emitted token
    prefill_comm_bytes: float = 0.0  # per-participant KV upload (paper §VII-A3)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class FedAttnEngine:
    """Greedy/sampling generation under the FedAttn protocol."""

    def __init__(
        self,
        config: ModelConfig,
        params=None,
        *,
        fedattn: Optional[FedAttnConfig] = None,
        backend: Optional[str] = None,
        bucket: str = "pow2",
        layers_mode: Optional[str] = None,
        mesh=None,
        kv_quant: Optional[str] = None,
    ):
        """params: the weights (loop or scan form), or None to install
        them later (:meth:`set_params` / :meth:`init_random_params`).

        bucket: 'pow2' pads L/n_new to power-of-two buckets so mixed
        request lengths share compiled executables; 'none' compiles per
        exact shape. layers_mode: None (auto), 'loop', or 'scan'.

        kv_quant: 'int8' / 'fp8' turns on the quantized KV representation
        (serving/quant.py): the scheduler's paged pool stores codes +
        per-page-per-head scales, and sync-layer exchange ships compressed
        rows (overrides ``fedattn.kv_quant``; the per-sync-layer byte
        accounting follows). 'none'/None leaves the compute dtype.

        mesh: a jax Mesh with a 'model' axis enables the SPMD serving mode
        of the continuous-batching scheduler (``generate_many``/
        ``ContinuousBatchingScheduler``): the KV slot pool is sharded over
        the 'model' axis along capacity and the resident decode step runs
        flash-decoding against it (distributed/spmd_attention). Standalone
        ``generate`` calls and admission prefills are not sharded — the
        mesh only changes where the pooled decode math runs, never its
        numbers (parity pinned in tests/test_spmd.py). The weights are
        replicated over the mesh once, when they are installed, so no
        call re-transfers them."""
        if config.is_encoder_decoder:
            raise NotImplementedError("engine currently drives decoder-only models")
        if bucket not in ("pow2", "none"):
            raise ValueError(f"unknown bucket policy {bucket!r}")
        self.config = config
        self.fed = fedattn if fedattn is not None else config.fedattn
        if kv_quant is not None:
            self.fed = self.fed.replace(kv_quant=kv_quant)
        self.kv_quant = None if self.fed.kv_quant == "none" else self.fed.kv_quant
        self.model = build_model(config)
        self.backend = backend
        self.bucket = bucket
        self.spmd = None
        if mesh is not None:
            from repro.distributed.runtime import SpmdContext

            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got {mesh.axis_names}"
                )
            # pool slots stay replicated (batch_axes=()); only the KV
            # capacity dim is sharded — the flash-decoding split
            self.spmd = SpmdContext(
                mesh=mesh, batch_axes=(), seq_axis="model",
                cache_axes=("model",),
            )
        self._schedule = self._build_schedule()
        self._plan = T.ScanPlan.from_schedule(config, self._schedule)
        if layers_mode not in (None, "loop", "scan"):
            raise ValueError(f"unknown layers_mode {layers_mode!r}")
        if layers_mode == "scan" and self._plan is None:
            raise ValueError(
                "layers_mode='scan' requires a sync schedule periodic over "
                "the layer body (ScanPlan.from_schedule returned None)"
            )
        self.layers_mode = layers_mode or ("scan" if self._plan else "loop")
        # bucketing L pads the *prefill* with segment -1 tokens: attention
        # masks them out of visibility, recurrences treat them as identity
        # state updates (the validity contract, models/ssm) — so every
        # causal stack buckets L; n_new always buckets (extra decode steps
        # run after the kept tokens and are discarded)
        self._bucket_L_ok = self.fed.causal
        self.params = None
        self._scan_params = None  # lazily stacked params for scan mode
        if params is not None:
            self.set_params(params)
        # compiled drivers, keyed by bucketed shapes + sampling mode only;
        # the guards carry the executable-budget contract (one charge per
        # distinct key — see repro.analysis.trace_guard)
        self._prefill_fns: dict = {}
        self._decode_fns: dict = {}
        self._trace_guards = {
            "prefill": TraceGuard("engine.prefill"),
            "decode": TraceGuard("engine.decode"),
        }

    # -- weights ----------------------------------------------------------------

    def set_params(self, params) -> None:
        """Install weights (loop or scan form); under a serving mesh they
        are replicated over it here, once."""
        if self.spmd is not None:
            replicated = NamedSharding(self.spmd.mesh, PartitionSpec())
            params = jax.device_put(params, replicated)
        self.params = params
        self._scan_params = None

    def init_random_params(self, seed: int) -> None:
        """Install random weights drawn from ``seed`` by one jitted init,
        in the layout the compiled drivers consume: scan form
        (``transformer.init_stacked`` — one resident copy, nothing to
        restack) when the layers lower as a scan over the pattern period,
        loop form otherwise. Scan-form weights serve the compiled paths
        only (``generate(compile=False)`` needs loop form)."""
        if self.layers_mode == "scan" and self._plan.period == len(self.config.pattern):
            init = functools.partial(T.init_stacked, self.model)
        else:
            init = self.model.init
        self.set_params(jax.jit(init)(jax.random.key(seed)))

    # -- protocol setup ---------------------------------------------------------

    def _build_schedule(self):
        sched = schedule_from_config(self.config)
        if self.fed.schedule != "uniform":
            from repro.core.schedule import SyncSchedule

            sched = SyncSchedule.by_name(
                self.fed.schedule, self.config.n_layers,
                interval=self.fed.sync_interval,
            )
        elif sched.n_syncs == 0:
            # The pattern carries no structural sync flags (e.g. a plain
            # homogeneous stack): 'uniform' then means every H-th layer, not
            # LocAttn — previously this silently degenerated to zero sync
            # layers, making sync_interval a no-op (want LocAttn? use
            # schedule='none').
            from repro.core.schedule import SyncSchedule

            sched = SyncSchedule.uniform(
                self.config.n_layers, self.fed.sync_interval
            )
        return sched

    def build_context(
        self,
        seq_len: int,
        *,
        partition: Optional[Partition] = None,
        rng: Optional[jax.Array] = None,
    ) -> FedAttnContext:
        return FedAttnContext.build(
            self.fed, self.config.n_layers, seq_len,
            partition=partition or Partition.contiguous(seq_len, self.fed.n_participants),
            schedule=self._schedule, rng=rng,
        )

    def _proto_ctx(self, capacity: int) -> FedAttnContext:
        """Decode-shaped context whose non-array fields (config, schedule)
        the compiled drivers bake in; every array field is overridden by
        traced per-call arguments. Built with full exchange so no rng is
        needed (the real contribution masks arrive as traced args)."""
        fed = self.fed.replace(kv_exchange_ratio=1.0)
        ctx = FedAttnContext.build(
            fed, self.config.n_layers, capacity,
            partition=Partition.contiguous(capacity, fed.n_participants),
            schedule=self._schedule,
        )
        return ctx.decode_template(capacity)

    @property
    def compile_counts(self) -> dict:
        """Number of cached compiled drivers — the recompile metric, read
        from the executable-budget guards (repro.analysis.trace_guard)."""
        return {
            "prefill": self._trace_guards["prefill"].count,
            "decode": self._trace_guards["decode"].count,
        }

    def decode_trace_size(self, B: int, L: int, n_new: int, *, sampled: bool = False) -> int:
        """Length of the decode driver's pretty-printed jaxpr — a proxy for
        traced-HLO size. O(period) in scan mode (depth-independent),
        O(n_layers) in loop mode; tests/benchmarks pin the scaling."""
        Lp, Nb = self._bucket_len(L), self._bucket_new(n_new)
        capacity = Lp + Nb
        plan = self._plan if self.layers_mode == "scan" else None
        cache = self.model.init_cache(B, capacity, plan=plan)
        fn = self._decode_fn(B, capacity, Nb, sampled)
        d0 = self.build_context(L).decode_template(capacity)
        jaxpr = jax.make_jaxpr(fn)(
            self._run_params(), cache, jnp.zeros((B,), jnp.int32),
            jnp.int32(L), jax.random.key(0), jnp.float32(1.0),
            d0.positions, d0.segments, d0.kv_positions, d0.kv_segments,
        )
        return len(str(jaxpr))

    def _run_params(self):
        """Params in the layout the compiled drivers consume."""
        if self.layers_mode != "scan":
            return self.params
        if self._scan_params is None:
            if "stacked" in self.params:
                # already scan-form (init_stacked) — no second weight copy,
                # but the stacking period must match the plan's
                if self._plan.period != len(self.config.pattern):
                    raise ValueError(
                        "scan-form params are stacked by the pattern period "
                        f"({len(self.config.pattern)}) but the schedule's "
                        f"scan unit is {self._plan.period} layers; pass "
                        "loop-form params and let the engine restack"
                    )
                self._scan_params = self.params
            else:
                self._scan_params = T.stack_params(
                    self.params, self.config, self._plan.period
                )
        return self._scan_params

    def _bucket_len(self, L: int) -> int:
        if self.bucket == "pow2" and self._bucket_L_ok:
            return _next_pow2(L)
        return L

    def _bucket_new(self, n_new: int) -> int:
        if self.bucket == "pow2":
            return _next_pow2(n_new)
        return n_new

    # -- generation ---------------------------------------------------------------

    def generate(
        self,
        tokens: jnp.ndarray,  # (B, L) global input sequence (assembled)
        n_new: int,
        *,
        partition: Optional[Partition] = None,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        extra_embeds: Optional[jnp.ndarray] = None,
        compile: bool = True,
    ) -> GenerationResult:
        B, L = tokens.shape
        ctx = self.build_context(L, partition=partition, rng=rng)
        sampled = temperature > 0.0 and rng is not None

        if compile:
            Lp = self._bucket_len(L)
            Nb = self._bucket_new(n_new)
            capacity = Lp + Nb
            plan = self._plan if self.layers_mode == "scan" else None
            cache = self.model.init_cache(B, capacity, plan=plan)
            last, cache = self._prefill_compiled(
                tokens, ctx, cache, extra_embeds, L, Lp, capacity
            )
        else:
            capacity = L + n_new
            cache = self.model.init_cache(B, capacity)
            logits, cache = self._prefill(tokens, ctx, cache, extra_embeds)
            last = logits[:, -1]

        tok0 = self._sample(last, temperature, rng, 0)
        lp0 = _token_logprob(last, tok0)
        if n_new == 1:
            # Single-token requests end at the prefill: no decode driver is
            # built AND no decode-template arrays are constructed — the
            # guard is the same for compiled and eager paths, and the token/
            # logprob must equal the first step of any longer run (pinned in
            # tests/test_engine_decode.py::test_n_new_1_matches_longer_run).
            toks, lps = tok0[:, None], lp0[:, None]
        else:
            dctx0 = ctx.decode_template(capacity)
            if compile:
                fn = self._decode_fn(B, capacity, Nb, sampled)
                rng_arg = rng if rng is not None else jax.random.key(0)
                rest_toks, rest_lps, cache = fn(
                    self._run_params(), cache, tok0, jnp.int32(L), rng_arg,
                    jnp.float32(max(temperature, 1e-6)),
                    dctx0.positions, dctx0.segments,
                    dctx0.kv_positions, dctx0.kv_segments,
                )
                rest_toks = rest_toks[:, : n_new - 1]
                rest_lps = rest_lps[:, : n_new - 1]
            else:
                rest_toks, rest_lps, cache = self._eager_decode(
                    cache, tok0, L, n_new, ctx, dctx0, temperature, rng
                )
            toks = jnp.concatenate([tok0[:, None], rest_toks], axis=1)
            lps = jnp.concatenate([lp0[:, None], rest_lps], axis=1)

        comm = ctx.comm_bytes_per_participant(
            self.config.n_kv_heads, self.config.head_dim
        )
        return GenerationResult(
            tokens=np.asarray(toks),
            logprobs=np.asarray(lps),
            prefill_comm_bytes=comm,
        )

    def generate_many(
        self,
        requests,  # Sequence[repro.serving.scheduler.Request]
        *,
        max_slots: int = 8,
        capacity: Optional[int] = None,
        steps_per_admit: int = 1,
        arrival_times=None,
        **scheduler_kwargs,
    ) -> list:
        """Serve many single-sequence requests through the continuous-
        batching scheduler (serving/scheduler.py): admissions fill a fixed
        ``(max_slots, capacity)`` KV slot pool and ONE resident decode
        executable steps every in-flight request together, retiring and
        re-admitting mid-flight. Per-request outputs match the equivalent
        standalone ``generate`` calls (same seed/partition).

        ``capacity=None`` sizes the pool exactly for the largest request —
        ``max(bucketed prefill length, L + n_new)`` over the batch
        (ContinuousBatchingScheduler.capacity_for). ``arrival_times`` are
        perf_counter offsets from call time (Poisson traces etc.); None
        admits everything as slots free up."""
        from repro.serving.scheduler import ContinuousBatchingScheduler

        if capacity is None:
            capacity = ContinuousBatchingScheduler.capacity_for(self, requests)
        sched = ContinuousBatchingScheduler(
            self, max_slots=max_slots, capacity=capacity,
            steps_per_admit=steps_per_admit, **scheduler_kwargs,
        )
        return sched.run(requests, arrival_times=arrival_times)

    # -- prefill ------------------------------------------------------------------

    def _round_of(self, layer: int) -> int:
        """Communication-round index of the sync at ``layer`` — the single
        numbering both the eager and the compiled prefill use (mirrors
        FedAttnContext._round_of_layer on the engine's schedule)."""
        return sum(1 for m in range(layer) if self._schedule.mask[m])

    def _layer_contrib(self, ctx: FedAttnContext, layer: int, capacity: int):
        """This layer's sparse-exchange row, padded to the cache capacity
        (None at local layers / full exchange)."""
        if ctx.contributed is None or not self._schedule.is_sync(layer):
            return None
        row = ctx.contributed[self._round_of(layer) % ctx.contributed.shape[0]]
        return jnp.pad(row, (0, capacity - row.shape[0]), constant_values=False)

    def _prefill(self, tokens, ctx, cache, extra_embeds):
        """Eager reference prefill: run the FedAttn forward and seed the
        cache by bulk decode-writes — the decode path with cache_len=0 and
        S_new=L reproduces prefill attention exactly (identical visibility
        masks, including the per-round sparse-exchange rows)."""
        B, L = tokens.shape
        capacity = _capacity(cache)
        dctx = ctx.for_decode_step(capacity, 0, n_new=L)
        dctx = dataclasses.replace(
            dctx,
            positions=ctx.positions,
            segments=ctx.segments,
        )
        cfg = self.config
        x = self.model._embed(self.params, tokens, extra_embeds)
        for m, (p, spec) in enumerate(zip(self.params["layers"], cfg.layer_specs())):
            x, cache[m] = T.apply_layer_decode(
                p, cache[m], x, 0, dctx, m, spec, cfg, backend=self.backend,
                contributed=self._layer_contrib(ctx, m, capacity),
            )
        x = LY.apply_norm(self.params["final_norm"], x, cfg)
        logits = LY.apply_lm_head(self.params["head"], self.params["embed"], x, cfg)
        return logits, cache

    def _prefill_compiled(self, tokens, ctx, cache, extra_embeds, L, Lp, capacity):
        """Pad the request into its bucket and run the jitted prefill.
        Returns (last-position logits (B, V), seeded cache)."""
        B = tokens.shape[0]
        pad = Lp - L
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        q_pos = jnp.arange(Lp, dtype=jnp.int32)
        q_seg = jnp.pad(ctx.segments, (0, pad), constant_values=PAD_SEGMENT)
        dctx0 = ctx.for_decode_step(capacity, 0)
        contrib = None
        if ctx.contributed is not None:
            contrib = jnp.pad(
                ctx.contributed,
                ((0, 0), (0, capacity - ctx.contributed.shape[1])),
                constant_values=False,
            )
        n_rounds = None if contrib is None else contrib.shape[0]
        fn = self._prefill_fn(B, Lp, capacity, n_rounds, extra_embeds is not None)
        return fn(
            self._run_params(), cache, tokens, jnp.int32(L),
            q_pos, q_seg, dctx0.kv_positions, dctx0.kv_segments,
            contrib, extra_embeds,
        )

    def _prefill_fn(self, B, Lp, capacity, n_rounds, has_extra,
                    per_row: bool = False):
        """Build (or fetch) the jitted bucketed prefill.

        The closure bakes in engine-invariant state only (config, schedule,
        layers mode); tokens, the real length, position/segment vectors and
        contribution masks are traced arguments — any request in the same
        (B, Lp, capacity) bucket reuses the executable.

        ``per_row`` is the coalesced-admission variant (scheduler): every
        row is an independent request, so ``real_len`` is a (B,) vector,
        ``q_seg``/``kv_seg`` are per-row ((B, Lp) / (B, capacity)) and
        ``contributed`` is (B, rounds, capacity) — the batched-vector
        contract of repro.kernels.core carries them through every backend.
        The LM head then gathers each row's own last real position."""
        key = (B, Lp, capacity, n_rounds, has_extra, per_row)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn

        model, backend, cfg = self.model, self.backend, self.config
        schedule, plan = self._schedule, self._plan
        scan = self.layers_mode == "scan"
        proto = self._proto_ctx(capacity)
        round_of = [self._round_of(m) for m in range(cfg.n_layers)]

        def run(params, cache, tokens, real_len, q_pos, q_seg, kv_pos, kv_seg,
                contributed, extra):
            if contributed is not None and contributed.ndim == 3:
                # (B, rounds, capacity) → rounds-first, (rounds, B, capacity)
                contributed = jnp.swapaxes(contributed, 0, 1)
            dctx = dataclasses.replace(
                proto, positions=q_pos, segments=q_seg,
                kv_positions=kv_pos, kv_segments=kv_seg, contributed=None,
            )
            x = model._embed(params, tokens, extra)
            if scan:
                x, cache = T.apply_layers_decode_scan(
                    params, cache, x, 0, dctx, cfg, plan,
                    backend=backend, contributed=contributed,
                )
            else:
                for m, (p, spec) in enumerate(zip(params["layers"], cfg.layer_specs())):
                    row = None
                    if contributed is not None and schedule.is_sync(m):
                        row = contributed[round_of[m] % n_rounds]
                    x, cache[m] = T.apply_layer_decode(
                        p, cache[m], x, 0, dctx, m, spec, cfg,
                        backend=backend, contributed=row,
                    )
            # LM head on the last real position only (L may be < Lp)
            if per_row:
                x = jnp.take_along_axis(x, (real_len - 1)[:, None, None], axis=1)
            else:
                x = jax.lax.dynamic_slice_in_dim(x, real_len - 1, 1, axis=1)
            x = LY.apply_norm(params["final_norm"], x, cfg)
            logits = LY.apply_lm_head(params["head"], params["embed"], x, cfg)
            return logits[:, 0], cache

        self._trace_guards["prefill"].charge(key)
        fn = jax.jit(run, donate_argnums=_donation_for_backend((1,)))
        self._prefill_fns[key] = fn
        return fn

    def _suffix_prefill_fn(self, B, Ls, capacity, n_rounds):
        """Build (or fetch) the jitted *suffix* prefill for prefix-cache
        hits (paged scheduler): each row's cached prefix KV is gathered
        from the physical pool through its source page table into a dense
        transient cache, and only the suffix tokens run through the
        layers — at traced per-row write frontiers ``write_lo`` (the
        prefix lengths), so one executable serves every (bucketed-suffix,
        capacity) combination regardless of where prefixes end. Always
        per-row (coalesced admission semantics: ``real_len`` is each
        row's true suffix length, the LM head gathers that position).

        The pool is NOT donated — the caller keeps using it; the returned
        transient goes through the same paged slot write as a fresh
        admission. Distinct from ``_prefill_fn`` because the bucketed
        full prefill bakes ``cache_len=0`` into its trace; the "suffix"
        key tag keeps the two executable families apart in
        ``_prefill_fns`` (and in the scheduler's batch-size reuse scan)."""
        key = (B, Ls, capacity, n_rounds, False, "suffix")
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn

        model, backend, cfg = self.model, self.backend, self.config
        schedule, plan = self._schedule, self._plan
        scan = self.layers_mode == "scan"
        proto = self._proto_ctx(capacity)
        round_of = [self._round_of(m) for m in range(cfg.n_layers)]

        def run(params, pool, src_pages, tokens, real_len, write_lo,
                q_seg, kv_pos, kv_seg, contributed):
            if contributed is not None and contributed.ndim == 3:
                contributed = jnp.swapaxes(contributed, 0, 1)
            cache = T.gather_paged_cache(pool, src_pages)
            q_pos = write_lo[:, None] + jnp.arange(Ls, dtype=jnp.int32)[None, :]
            dctx = dataclasses.replace(
                proto, positions=q_pos, segments=q_seg,
                kv_positions=kv_pos, kv_segments=kv_seg, contributed=None,
            )
            x = model._embed(params, tokens, None)
            if scan:
                x, cache = T.apply_layers_decode_scan(
                    params, cache, x, write_lo, dctx, cfg, plan,
                    backend=backend, contributed=contributed,
                )
            else:
                for m, (p, spec) in enumerate(zip(params["layers"], cfg.layer_specs())):
                    row = None
                    if contributed is not None and schedule.is_sync(m):
                        row = contributed[round_of[m] % n_rounds]
                    x, cache[m] = T.apply_layer_decode(
                        p, cache[m], x, write_lo, dctx, m, spec, cfg,
                        backend=backend, contributed=row,
                    )
            x = jnp.take_along_axis(x, (real_len - 1)[:, None, None], axis=1)
            x = LY.apply_norm(params["final_norm"], x, cfg)
            logits = LY.apply_lm_head(params["head"], params["embed"], x, cfg)
            return logits[:, 0], cache

        self._trace_guards["prefill"].charge(key)
        fn = jax.jit(run, donate_argnums=_donation_for_backend(()))
        self._prefill_fns[key] = fn
        return fn

    # -- decode -------------------------------------------------------------------

    def _decode_fn(self, B: int, capacity: int, n_steps: int, sampled: bool):
        """Build (or fetch) the jitted multi-token decode driver.

        The closure only bakes in engine-invariant state (model config,
        sync schedule, layers mode, backend) plus the static key (bucketed
        shapes, sampling mode). Everything that varies call-to-call —
        params, cache, first token, the real prefill length, rng,
        temperature, and the decode-context vectors derived from the
        partition — is a traced argument, so reusing a cached executable is
        always sound: sweeping the temperature, the partition, or any L in
        the bucket never recompiles."""
        key = (B, capacity, n_steps, sampled)
        fn = self._decode_fns.get(key)
        if fn is not None:
            return fn

        model, backend = self.model, self.backend
        mode, plan = self.layers_mode, self._plan
        # Proto context: engine-fixed config/schedule objects; array fields
        # are all overridden below.
        proto = self._proto_ctx(capacity)

        def run(params, cache, tok0, real_len, rng, temp,
                q_pos0, q_seg, kv_pos, kv_seg):
            tpl = dataclasses.replace(
                proto, positions=q_pos0, segments=q_seg,
                kv_positions=kv_pos, kv_segments=kv_seg, contributed=None,
            )

            def body(carry, step):
                cache, tok = carry
                dctx = dataclasses.replace(tpl, positions=q_pos0 + step)
                logits, cache = model.decode_step(
                    params, cache, tok[:, None], real_len + step, tpl,
                    step=step, backend=backend, dctx=dctx, mode=mode,
                    plan=plan,
                )
                nxt_logits = logits[:, -1]
                if sampled:
                    r = jax.random.fold_in(rng, step + 1)
                    nxt = jax.random.categorical(
                        r, nxt_logits.astype(jnp.float32) / temp
                    )
                else:
                    nxt = jnp.argmax(nxt_logits, axis=-1)
                return (cache, nxt), (nxt, _token_logprob(nxt_logits, nxt))

            (cache, _), (toks, lps) = jax.lax.scan(
                body, (cache, tok0), jnp.arange(n_steps - 1)
            )
            return toks.T, lps.T, cache  # (B, n_steps-1) each

        # Donate the cache so the compiled step updates it in place.
        self._trace_guards["decode"].charge(key)
        fn = jax.jit(run, donate_argnums=_donation_for_backend((1,)))
        self._decode_fns[key] = fn
        return fn

    def _eager_decode(self, cache, tok0, L, n_new, ctx, dctx0, temperature, rng):
        """Reference per-token Python loop (`compile=False` fallback)."""
        tok = tok0
        out_tokens, out_lps = [], []
        for step in range(n_new - 1):
            dctx = dataclasses.replace(dctx0, positions=dctx0.positions + step)
            logits, cache = self.model.decode_step(
                self.params, cache, tok[:, None], L + step, ctx, step=step,
                backend=self.backend, dctx=dctx,
            )
            last = logits[:, -1]
            tok = self._sample(last, temperature, rng, step + 1)
            out_tokens.append(tok)
            out_lps.append(_token_logprob(last, tok))
        return (
            jnp.stack(out_tokens, axis=1),
            jnp.stack(out_lps, axis=1),
            cache,
        )

    def _sample(self, logits, temperature, rng, step):
        if temperature <= 0.0 or rng is None:
            return jnp.argmax(logits, axis=-1)
        r = jax.random.fold_in(rng, step)
        return jax.random.categorical(r, logits.astype(jnp.float32) / temperature)


def _token_logprob(logits: jnp.ndarray, tok: jnp.ndarray) -> jnp.ndarray:
    """(B,) log p(tok | prefix) under the model's (untempered) softmax."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


def _verify_candidates(logits, draft, temps, keys, fold, sampled):
    """Candidate tokens + accept lengths for the pool's speculative verify
    step (scheduler._verify_fn — the verify executable family; jitted with
    the same ``_donation_for_backend`` pool-donation wiring as the
    non-speculative step).

    ``logits`` is ``(S, k+1, V)`` from ONE multi-token decode forward over
    the query block ``[last_tok, d_1 .. d_k]`` at positions ``frontier ..
    frontier+k``: row ``i`` scores the token that follows the prefix
    extended by ``d_1..d_i``. ``cand[s, i]`` is the token the
    NON-speculative schedule would emit at that point — greedy rows take
    the raw-logit argmax, sampled rows draw ``categorical(fold_in(key,
    fold+i), logits/temp)``, the exact per-token key schedule of
    ``_step_fn``/generate — which is what makes token-exact acceptance
    lossless for sampled streams too (an accepted draft IS the token the
    sequential run would have drawn).  ``accept[s]`` counts the leading
    draft tokens equal to their candidate (``cumprod`` of the match mask),
    so the tick emits ``cand[s, :accept[s]+1]``: the accepted drafts plus
    the one correction/bonus token whose logits are already in hand.
    ``lps`` are the untempered log-softmax logprobs of every candidate
    (same definition as :func:`_token_logprob`).
    """
    k1 = logits.shape[1]
    greedy = jnp.argmax(logits, axis=-1)
    steps = fold[:, None] + jnp.arange(k1, dtype=fold.dtype)[None, :]
    folded = jax.vmap(
        lambda key, st: jax.vmap(lambda s: jax.random.fold_in(key, s))(st)
    )(keys, steps)

    def _cat_row(keys_row, logits_row, t):
        return jax.vmap(
            lambda r, l: jax.random.categorical(r, l.astype(jnp.float32) / t)
        )(keys_row, logits_row)

    cat = jax.vmap(_cat_row)(folded, logits, temps)
    cand = jnp.where(sampled[:, None], cat, greedy)
    match = (draft == cand[:, :-1]).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lps = jnp.take_along_axis(lp, cand[..., None], axis=-1)[..., 0]
    return cand, lps, accept


def _capacity(cache) -> int:
    for c in cache:
        if "k" in c:
            return c["k"].shape[1]
    return 1
