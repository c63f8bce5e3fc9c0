"""Serving throughput: continuous batching (KV slot pool + one resident
decode executable, repro/serving/scheduler.py) vs sequential generate()
calls, on a mixed-length Poisson arrival trace.

This is the PR-3 acceptance benchmark: the scheduler must deliver >=2x the
aggregate tok/s of serving the same trace one request at a time — the win
comes from streaming the weights once per step for every in-flight request
instead of once per request, and from short requests no longer queueing
behind long ones. The decode executable count must stay at 1 across the
whole trace (admission/retirement never recompiles).

Both passes replay the SAME arrival trace (exponential gaps) and are
warmed up first, so the timed numbers are steady-state serving. The
scheduler results are also checked token-exact against the sequential
ones — the throughput claim is only meaningful if interleaving preserves
per-request outputs.

The ``serving_hybrid_jamba_bucketing`` record replays a mixed-length trace
through a jamba-style mamba+attention pool with L-bucketing on vs off: the
recurrence validity contract (repro/kernels/core docstring) made pow2
buckets legal for SSM/hybrid stacks, collapsing the per-exact-L admission
prefill executables into per-bucket ones (both counts CI-gated).

The ``serving_spec_decode`` record replays a repetitive-completion trace
through a speculative pool (``spec_k=3``, host n-gram drafter + one
multi-token verify executable) paired adjacently against a ``spec_k=0``
pool: the gated ``speedup`` is the per-request decode-rate ratio at the
measured draft acceptance rate, and ``verify_step_executables`` pins the
verify step to ONE executable across draft/accept churn.

The ``serving_quant_kv`` record replays a mixed-length greedy trace
through an int8-quantized paged pool (per-page-per-kv-head scales,
repro/serving/quant.py) paired adjacently against the f32 paged pool:
the gated ``speedup`` is the pool-bytes-per-resident-token ratio (a
deterministic within-run pairing — both pools serve the SAME trace at
the same page budget, so a drop means the quantized pool layout grew),
``parity_mismatches`` pins greedy token equality against the f32 pool,
the exchange-codec shrink vs f32 wire rows is recorded, and the
executable counts pin zero-recompile churn (scales are data, not
shapes).

The ``serving_paged_flash`` record replays a mixed-length greedy trace
through TWO paged pools adjacently — the XLA gather/densify read path vs
the fused Pallas flash-decode backend (repro/kernels/flash_decode.py,
``backend='pallas'``) — and gates greedy token parity, the single fused
decode executable, and the paired tok/s ratio. On CPU the kernel runs
under the Pallas *interpreter*, so the honest ratio is BELOW 1x (the
record pins correctness + zero-recompile churn and tracks the ratio as
a trend; the compiled-kernel win is a TPU number).

``--mesh N`` additionally measures the SPMD pooled path: the same trace
through a pool whose KV capacity is sharded over an N-way 'model' mesh
(flash-decoding partial-softmax per shard + one psum,
repro/distributed/spmd_attention.py), paired adjacently against the
single-device pool. Needs N devices — on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launching
(the CI slow job does; without enough devices the mesh record is skipped
with a note). On a shared-CPU box the mesh ratio measures collective
overhead, not a speedup — the record exists to pin executable counts and
parity under the mesh, and to become a real trend once CI runs on
multi-device hardware.

Prints ``name,us_per_call,derived`` CSV lines (us per generated token) and
returns records for BENCH_serving.json (benchmarks/run.py).

Usage:
  PYTHONPATH=src python -m benchmarks.serving_throughput [--requests 12]
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      PYTHONPATH=src python -m benchmarks.serving_throughput --mesh 2
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from common import bench_config, csv_line  # noqa: E402

from repro.kernels.core import pallas_interpret  # noqa: E402
from repro.launch.serve import poisson_trace  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import FedAttnEngine, NGramDrafter  # noqa: E402
from repro.serving.scheduler import ContinuousBatchingScheduler  # noqa: E402
from repro.types import FedAttnConfig, LayerSpec  # noqa: E402


def _sequential_pass(engine, reqs, arrivals, *, timed: bool):
    """Serve the trace one generate() call at a time, in arrival order,
    never starting a request before it arrives. Returns (results, wall)."""
    results = []
    t0 = time.perf_counter()
    for req, at in zip(reqs, arrivals):
        if timed:
            now = time.perf_counter() - t0
            if now < at:
                time.sleep(at - now)
        results.append(
            engine.generate(
                req.tokens[None], req.n_new,
                temperature=req.temperature, rng=req.rng,
            )
        )
    return results, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-slots", type=int, default=6,
                    help="pool rows; on this 2-vCPU CPU, 5-6 slots is the "
                         "sweet spot (enough batching to amortize the "
                         "per-step weight stream, small enough that the "
                         "drain tail and inactive rows stay cheap)")
    ap.add_argument("--steps-per-admit", type=int, default=6,
                    help="fused decode sub-steps per tick (amortizes "
                         "dispatch + host bookkeeping)")
    ap.add_argument("--arrival-rate", type=float, default=200.0,
                    help="Poisson arrival rate (req/s). Must oversubscribe "
                         "the pool so BOTH passes are compute-bound — at "
                         "rates the sequential path can keep up with, "
                         "aggregate tok/s measures the arrival process, "
                         "not the serving architecture")
    ap.add_argument("--mesh", type=int, default=0,
                    help="also measure the SPMD pooled path over an N-way "
                         "'model' mesh (capacity-sharded KV pool); skipped "
                         "with a note when fewer than N devices exist")
    args, _ = ap.parse_known_args()  # tolerate benchmarks/run.py flags

    cfg = bench_config(n_layers=4)
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    engine = FedAttnEngine(cfg, params, fedattn=fed)

    # Mixed lengths spanning two pow2 buckets each way (L in [17, 64] ->
    # prefill buckets {32, 64}; n_new in [9, 32] -> decode buckets {16, 32})
    rng = np.random.default_rng(0)
    reqs, arrivals = poisson_trace(
        rng, args.requests, vocab_size=cfg.vocab_size, max_len=64,
        max_new=32, rate_per_s=args.arrival_rate,
    )
    reqs = [
        type(r)(
            tokens=(r.tokens if r.tokens.shape[0] > 16
                    else jax.numpy.tile(r.tokens, 2)[:17]),
            n_new=max(r.n_new, 9), temperature=r.temperature, rng=r.rng,
        )
        for r in reqs
    ]
    total_new = sum(r.n_new for r in reqs)

    capacity = ContinuousBatchingScheduler.capacity_for(engine, reqs)

    # --- timed passes: paired rounds ---------------------------------------
    # Wall times on the shared 2-vCPU box drift ~2x over minutes, so the
    # two passes are measured ADJACENTLY in each round and the speedup is
    # the median of the per-round (paired) ratios — drift cancels instead
    # of inflating or deflating the comparison.
    sched = ContinuousBatchingScheduler(
        engine, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=args.steps_per_admit,
    )
    _sequential_pass(engine, reqs, arrivals, timed=False)  # warmup/compile
    sched.run(reqs)  # warmup: compiles every pool executable/bucket once
    rounds = []
    for _ in range(3):
        seq_res, w_seq = _sequential_pass(engine, reqs, arrivals, timed=True)
        t0 = time.perf_counter()
        stream_res = sched.run(reqs, arrival_times=arrivals)
        w_pool = time.perf_counter() - t0
        rounds.append((w_seq / w_pool, w_seq, w_pool))
    rounds.sort()
    _, wall_seq, wall_stream = rounds[len(rounds) // 2]  # median-ratio round
    tok_s_seq = total_new / wall_seq
    tok_s_stream = total_new / wall_stream
    n_decode_execs = sched.compile_counts["decode_step"]

    # interleaving must preserve per-request outputs exactly
    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(stream_res, seq_res)
    )

    speedup = tok_s_stream / tok_s_seq
    name = f"serving_stream_N{fed.n_participants}_H{fed.sync_interval}"
    print(csv_line(f"{name}_sequential", 1e6 / tok_s_seq,
                   f"tok_s={tok_s_seq:.1f}"))
    print(csv_line(f"{name}_pool", 1e6 / tok_s_stream,
                   f"tok_s={tok_s_stream:.1f},speedup={speedup:.2f}x,"
                   f"slots={args.max_slots},k={args.steps_per_admit},"
                   f"decode_execs={n_decode_execs},mismatches={mismatches}"))
    print(f"# continuous batching {speedup:.2f}x sequential aggregate tok/s "
          f"({total_new} tokens, {len(reqs)} requests, pool "
          f"{args.max_slots}x{capacity})")
    if speedup < 2.0:
        print("# WARNING: speedup below the 2x floor this repo pins")
    if n_decode_execs != 1:
        print(f"# WARNING: decode_step executables = {n_decode_execs} "
              "(expected 1 — admission/retirement must not recompile)")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged from sequential")
    records = [{
        "name": name,
        # speedup is a PAIRED within-run ratio (adjacent passes, median
        # round) — machine drift cancels, so compare_bench.py gates on it
        "paired_ratio": True,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "arrival_rate_per_s": args.arrival_rate,
        "max_slots": args.max_slots,
        "steps_per_admit": args.steps_per_admit,
        "capacity": capacity,
        "scan_plan": (engine._plan.period, engine._plan.n_periods),
        "tok_s_sequential": tok_s_seq,
        "tok_s_stream": tok_s_stream,
        "speedup": speedup,
        "decode_step_executables": n_decode_execs,
        "parity_mismatches": mismatches,
    }]

    records += _hybrid_pass(args)
    records += _paged_prefix_pass(args)
    records += _spec_pass(args)
    records += _quant_pass(args)
    records += _paged_flash_pass(args)

    if args.mesh:
        if len(jax.devices()) < args.mesh:
            print(f"# --mesh {args.mesh} skipped: only {len(jax.devices())} "
                  "device(s) (set XLA_FLAGS=--xla_force_host_platform_"
                  f"device_count={args.mesh} before launching)")
        else:
            records += _mesh_pass(
                cfg, fed, params, reqs, args, total_new, stream_res
            )
    return records


def _hybrid_pass(args):
    """Hybrid (jamba-style mamba+attention) stack through the pool with
    L-bucketing ON vs OFF — the recurrence validity contract made pow2
    buckets legal for SSM/hybrid stacks, and the HEADLINE metric is the
    prefill-executable collapse: ``bucket='none'`` compiles one admission
    prefill per exact (B, L) while ``bucket='pow2'`` compiles one per
    (B-bucket, L-bucket). Both executable counts are deterministic (pure
    python-side cache keys over a fixed trace) and CI-gated via
    compare_bench's *_executables rule; tok/s are info/warn-only on this
    shared box. Token parity between the two policies is asserted — the
    collapse is only a win because padded tokens are identity state
    updates (pinned at kernel level in tests/test_ssm_masking.py)."""
    cfg = bench_config(n_layers=4).replace(
        name="bench-jamba",
        arch_type="hybrid",
        pattern=(LayerSpec(kind="mamba"), LayerSpec(sync=True)),
    )
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(7)
    n_req = min(args.requests, 16)  # bounded: 4 pool traces below
    reqs, _ = poisson_trace(
        rng, n_req, vocab_size=cfg.vocab_size, max_len=64, max_new=16,
        rate_per_s=args.arrival_rate,
    )
    total_new = sum(r.n_new for r in reqs)

    walls, scheds, results = {}, {}, {}
    for policy in ("pow2", "none"):
        eng = FedAttnEngine(cfg, params, fedattn=fed, bucket=policy)
        capacity = ContinuousBatchingScheduler.capacity_for(eng, reqs)
        sched = ContinuousBatchingScheduler(
            eng, max_slots=args.max_slots, capacity=capacity,
            steps_per_admit=args.steps_per_admit,
        )
        sched.run(reqs)  # warmup: compiles every admission/decode executable
        t0 = time.perf_counter()
        results[policy] = sched.run(reqs)
        walls[policy] = time.perf_counter() - t0
        scheds[policy] = sched

    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(results["pow2"], results["none"])
    )
    n_bucketed = scheds["pow2"].compile_counts["prefill"]
    n_exact = scheds["none"].compile_counts["prefill"]
    n_decode = scheds["pow2"].compile_counts["decode_step"]
    tok_s = {p: total_new / walls[p] for p in walls}
    name = "serving_hybrid_jamba_bucketing"
    print(csv_line(name, 1e6 / tok_s["pow2"],
                   f"tok_s={tok_s['pow2']:.1f},prefill_execs={n_bucketed}"
                   f"(vs {n_exact} unbucketed),decode_execs={n_decode},"
                   f"mismatches={mismatches}"))
    print(f"# hybrid stack L-bucketing: {n_exact} per-exact-L prefill "
          f"executables collapse to {n_bucketed} pow2-bucketed ones "
          f"({len(reqs)} mixed-length requests; {mismatches} token "
          "mismatches between policies)")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged between bucket "
              "policies (validity-contract violation)")
    return [{
        "name": name,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "max_slots": args.max_slots,
        "steps_per_admit": args.steps_per_admit,
        # the collapse headline — both CI-gated against growth
        "bucketed_prefill_executables": n_bucketed,
        "unbucketed_prefill_executables": n_exact,
        "decode_step_executables": n_decode,
        "tok_s_bucketed": tok_s["pow2"],
        "tok_s_unbucketed": tok_s["none"],
        "parity_mismatches": mismatches,
    }]


def _paged_prefix_pass(args):
    """Paged KV pool + prefix cache on a shared-system-prompt trace — the
    PR-7 acceptance benchmark. Every request opens with the SAME 48-token
    system prompt (6 exact pages of 8) followed by a distinct tail, the
    workload the prefix cache exists for. Three things are pinned:

    * ``admission_prefill_executables``: the handful of (B-bucket,
      L-bucket) full+suffix prefill executables that serve the whole
      mixed-tail trace to steady state (CI-gated via the generic
      ``*_executables`` rule in compare_bench.py), as is the single
      decode-step executable. (The crisp prefill-ONCE pin — one full +
      one suffix executable on a uniform-tail trace — lives in
      tests/test_paged_serving.py; this trace has mixed tails, so
      suffix lengths span a few buckets.)
    * ``prefix_hit_rate`` / ``prefill_tokens``: every request past the
      first coalesced tick maps the cached prompt pages copy-free and
      prefills only its tail — the token counter proves the shared
      prompt is NOT re-prefilled per request.
    * ``peak_bytes_per_resident_token``: the paged pool is sized at 2/3
      of the dense worst-case rows (num_pages=64 x page_size=8 vs
      6 slots x 128 rows) yet serves the same trace with the same peak
      residency — slots consume pages on demand instead of capacity
      rows, so pool bytes per resident token DROP.

    The timed pass runs after TWO warmup replays: the first populates
    the prefix cache (its admissions miss), the second compiles the
    hit-path suffix buckets the steady-state trace actually uses.
    Token/logprob parity against the dense pool is asserted (mismatches
    recorded); tok/s is trend-only on this shared box."""
    cfg = bench_config(n_layers=4)
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(11)
    n_req = min(args.requests, 16)
    sys_prompt = rng.integers(3, cfg.vocab_size, size=(48,))
    reqs = []
    for i in range(n_req):
        tail = rng.integers(3, cfg.vocab_size, size=(int(rng.integers(3, 9)),))
        reqs.append(type(poisson_trace(rng, 1, vocab_size=cfg.vocab_size,
                                       max_len=8, max_new=2,
                                       rate_per_s=1e9)[0][0])(
            tokens=jax.numpy.asarray(
                np.concatenate([sys_prompt, tail]), jax.numpy.int32),
            n_new=int(rng.integers(4, 9)),
        ))
    total_new = sum(r.n_new for r in reqs)
    capacity = 128

    eng_dense = FedAttnEngine(cfg, params, fedattn=fed)
    dense = ContinuousBatchingScheduler(
        eng_dense, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=args.steps_per_admit, kv_layout="dense",
    )
    dense_res = dense.run(reqs)

    eng = FedAttnEngine(cfg, params, fedattn=fed)  # fresh executable caches
    sched = ContinuousBatchingScheduler(
        eng, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=args.steps_per_admit,
        kv_layout="paged", page_size=8, num_pages=64, prefix_cache=True,
    )
    sched.run(reqs)  # warmup 1: populates the prefix cache (misses)
    sched.run(reqs)  # warmup 2: compiles the hit-path suffix buckets
    n_prefill_execs = eng.compile_counts["prefill"]
    n_decode_execs = sched.compile_counts["decode_step"]
    pre = sched.pool_stats()
    t0 = time.perf_counter()
    paged_res = sched.run(reqs)
    wall = time.perf_counter() - t0
    tok_s = total_new / wall
    if eng.compile_counts["prefill"] != n_prefill_execs:
        print("# WARNING: timed paged+prefix pass compiled "
              f"{eng.compile_counts['prefill'] - n_prefill_execs} new "
              "prefill executable(s) — not steady state")

    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(paged_res, dense_res)
    )
    st = sched.pool_stats()
    dst = dense.pool_stats()
    # per-replay (timed run only) counters — the cumulative ones span the
    # two warmups too, which the dense side did not run
    hits = st["prefix_hits"] - pre["prefix_hits"]
    misses = st["prefix_misses"] - pre["prefix_misses"]
    reused = st["prefix_tokens_reused"] - pre["prefix_tokens_reused"]
    prefill_toks = st["prefill_tokens"] - pre["prefill_tokens"]
    hit_rate = hits / max(1, hits + misses)
    name = "serving_paged_prefix"
    print(csv_line(name, 1e6 / tok_s,
                   f"tok_s={tok_s:.1f},prefill_execs={n_prefill_execs},"
                   f"hit_rate={hit_rate:.2f},prefill_toks={prefill_toks},"
                   f"mismatches={mismatches}"))
    print(f"# paged+prefix pool: {n_prefill_execs} prefill executables "
          f"({len(reqs)} requests sharing a {len(sys_prompt)}-token "
          f"prompt), {reused} prompt tokens reused/replay "
          f"({prefill_toks} prefilled vs {dst['prefill_tokens']} dense), "
          f"{st['peak_bytes_per_resident_token']:.0f} B/resident-token "
          f"(dense {dst['peak_bytes_per_resident_token']:.0f})")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged from dense")
    return [{
        "name": name,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "max_slots": args.max_slots,
        "capacity": capacity,
        "page_size": sched.page_size,
        "num_pages": sched.num_pages,
        # CI-gated: prefix cache means ONE full + ONE suffix prefill
        # executable for the whole shared-prompt trace
        "admission_prefill_executables": n_prefill_execs,
        "decode_step_executables": n_decode_execs,
        "prefix_hit_rate": hit_rate,
        "prefix_tokens_reused": reused,
        "prefill_tokens_paged": prefill_toks,
        "prefill_tokens_dense": dst["prefill_tokens"],
        "peak_bytes_per_resident_token_paged":
            st["peak_bytes_per_resident_token"],
        "peak_bytes_per_resident_token_dense":
            dst["peak_bytes_per_resident_token"],
        "tok_s_paged": tok_s,
        "parity_mismatches": mismatches,
    }]


def _spec_pass(args):
    """Speculative decoding through the pool on a repetitive-completion
    trace — the PR-8 acceptance benchmark. Prompts are tiled short motifs,
    so greedy continuations cycle and the host-side n-gram drafter locks
    on; each verify tick then advances a slot several tokens for one
    weight stream. Two pools serve the SAME trace adjacently per round:
    a baseline (``spec_k=0``, one token per tick) and a speculative one
    (``spec_k=3``, 6-gram drafter — deeper context disambiguates the
    quasi-periodic branch points in the model's greedy cycles, and a
    shorter draft keeps the verify step cheap enough that accepted
    tokens win), both at ``steps_per_admit=1`` so the comparison is
    per-weight-stream, and the headline ``speedup`` is the median
    per-round ratio of per-request decode rates (baseline TPOT p50 over
    speculative TPOT p50 from ``latency_stats``) — a paired within-run
    ratio, so compare_bench gates it (floor this repo pins: 1.3x at the
    measured acceptance rate). Also CI-gated: ``verify_step_executables``
    stays 1 across the whole churning trace (draft tokens and ragged
    accept lengths are traced data), and ``decode_step_executables``
    stays 0 — a speculative pool never builds the sequential step.
    Token/logprob parity against the baseline pool is asserted
    (mismatches recorded); the acceptance rate is trend-only."""
    cfg = bench_config(n_layers=4)
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(13)
    spec_k = 3
    n_req = min(args.requests, 12)
    proto = poisson_trace(rng, 1, vocab_size=cfg.vocab_size, max_len=8,
                          max_new=2, rate_per_s=1e9)[0][0]
    reqs = []
    for _ in range(n_req):
        motif = rng.integers(3, cfg.vocab_size, size=(int(rng.integers(3, 6)),))
        L = int(rng.integers(18, 33))
        reqs.append(type(proto)(
            tokens=jax.numpy.asarray(
                np.tile(motif, L // len(motif) + 1)[:L], jax.numpy.int32),
            n_new=96,
        ))
    total_new = sum(r.n_new for r in reqs)
    capacity = 160

    base = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params, fedattn=fed),
        max_slots=args.max_slots, capacity=capacity, steps_per_admit=1,
    )
    spec = ContinuousBatchingScheduler(
        FedAttnEngine(cfg, params, fedattn=fed),
        max_slots=args.max_slots, capacity=capacity, steps_per_admit=1,
        spec_k=spec_k, drafter=NGramDrafter(max_ngram=6),
    )
    base_res = base.run(reqs)  # warmup: compiles every pool executable
    spec_res = spec.run(reqs)
    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(spec_res, base_res)
    )
    base.latency_stats(reset=True)
    spec.latency_stats(reset=True)
    rounds = []
    for _ in range(3):
        base.run(reqs)
        b = base.latency_stats(reset=True)
        spec.run(reqs)
        s = spec.latency_stats(reset=True)
        rounds.append((b["tpot_p50"] / s["tpot_p50"],
                       b["tpot_p50"], s["tpot_p50"]))
    rounds.sort()
    speedup, tpot_base, tpot_spec = rounds[len(rounds) // 2]
    st = spec.pool_stats()
    accept = st["spec_acceptance_rate"]
    n_verify = spec.compile_counts["verify_step"]
    n_decode = spec.compile_counts["decode_step"]
    name = "serving_spec_decode"
    print(csv_line(name, 1e6 * tpot_spec,
                   f"tok_s_per_req={1.0 / tpot_spec:.1f},"
                   f"speedup={speedup:.2f}x,accept={accept:.2f},k={spec_k},"
                   f"verify_execs={n_verify},mismatches={mismatches}"))
    print(f"# speculative pool (k={spec_k}): {speedup:.2f}x the baseline "
          f"per-request decode rate at {accept:.0%} draft acceptance "
          f"({len(reqs)} requests x {reqs[0].n_new} tokens, "
          f"{st['verify_ticks']} verify ticks)")
    if speedup < 1.3:
        print("# WARNING: speculative speedup below the 1.3x floor this "
              "repo pins")
    if n_verify != 1 or n_decode != 0:
        print(f"# WARNING: spec pool executables verify={n_verify} "
              f"decode={n_decode} (expected 1/0 — draft churn must not "
              "recompile)")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged from the "
              "non-speculative pool")
    return [{
        "name": name,
        # speedup is a PAIRED within-run ratio of per-request TPOT p50s
        # (adjacent passes, median round) — compare_bench.py gates on it
        "paired_ratio": True,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "spec_k": spec_k,
        "max_slots": args.max_slots,
        "capacity": capacity,
        "acceptance_rate": accept,
        "tpot_ms_base_p50": tpot_base * 1e3,
        "tpot_ms_spec_p50": tpot_spec * 1e3,
        "speedup": speedup,
        "verify_step_executables": n_verify,
        "decode_step_executables": n_decode,
        "parity_mismatches": mismatches,
    }]


def _quant_pass(args):
    """Quantized paged pool on a mixed-length greedy trace — the PR-9
    acceptance benchmark. The SAME trace is served by an f32 paged pool
    and an int8 one (per-page-per-kv-head scales, repro/serving/quant.py)
    at the SAME page budget, and four things are pinned:

    * ``speedup`` (paired, CI-gated): pool bytes per peak resident token,
      f32 over int8. Both pools size identically in pages and serve the
      same residency, so the ratio is a deterministic layout property
      (~3.9x here: 4B rows -> 1B codes + two f32 scales per page-head);
      the repo floor is 2x resident tokens per pool byte.
    * ``parity_mismatches``: greedy tokens must match the f32 pool
      EXACTLY on this trace — dequant-at-gather keeps every consumer on
      the dense contract, and the per-page scale granularity keeps logit
      error ~1e-3, below the trace's greedy decision margins.
    * ``exchange_shrink_vs_f32``: the sync-layer wire codec
      (int8 rows + per-row-per-head f32 scales) vs plain f32 rows, from
      ``aggregation.exchange_bytes_per_row`` — 2*nkv*dh*4 over
      2*nkv*(dh+4) = 3.56x at dh=32, repo floor 3.5x.
    * ``*_executables``: admission prefill + decode step counts after
      warmup, and ``timed_replay_new_executables`` = 0 — scale updates
      are traced data, so quantized churn never recompiles.

    tok/s for both pools are recorded trend-only (dequant adds a gather
    multiply; on this CPU box the delta is noise)."""
    cfg = bench_config(n_layers=4)
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(17)
    n_req = min(args.requests, 16)
    proto = poisson_trace(rng, 1, vocab_size=cfg.vocab_size, max_len=8,
                          max_new=2, rate_per_s=1e9)[0][0]
    reqs = []
    for _ in range(n_req):  # greedy (temperature 0): parity is exact-match
        L = int(rng.integers(12, 49))
        reqs.append(type(proto)(
            tokens=jax.numpy.asarray(
                rng.integers(3, cfg.vocab_size, size=(L,)), jax.numpy.int32),
            n_new=int(rng.integers(6, 17)),
        ))
    total_new = sum(r.n_new for r in reqs)
    capacity = 128

    pools = {}
    for mode in ("none", "int8"):
        eng = FedAttnEngine(cfg, params, fedattn=fed)
        sched = ContinuousBatchingScheduler(
            eng, max_slots=args.max_slots, capacity=capacity,
            steps_per_admit=args.steps_per_admit,
            kv_layout="paged", page_size=8, num_pages=64, kv_quant=mode,
        )
        sched.run(reqs)  # warmup: compiles every pool executable
        n_pref = eng.compile_counts["prefill"]
        n_dec = sched.compile_counts["decode_step"]
        t0 = time.perf_counter()
        res = sched.run(reqs)
        wall = time.perf_counter() - t0
        pools[mode] = {
            "res": res, "stats": sched.pool_stats(), "wall": wall,
            "n_pref": n_pref, "n_dec": n_dec,
            "new": (eng.compile_counts["prefill"] - n_pref
                    + sched.compile_counts["decode_step"] - n_dec),
        }

    f32, q8 = pools["none"], pools["int8"]
    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(q8["res"], f32["res"])
    )
    bytes_ratio = (f32["stats"]["peak_bytes_per_resident_token"]
                   / q8["stats"]["peak_bytes_per_resident_token"])
    from repro.core.aggregation import exchange_bytes_per_row
    per_row_f32 = exchange_bytes_per_row(
        cfg.n_kv_heads, cfg.head_dim, "none", bytes_per_el=4)
    per_row_q8 = exchange_bytes_per_row(
        cfg.n_kv_heads, cfg.head_dim, "int8", bytes_per_el=4)
    xratio = per_row_f32 / per_row_q8
    tok_s = {m: total_new / pools[m]["wall"] for m in pools}
    new_execs = q8["new"] + f32["new"]
    name = "serving_quant_kv"
    print(csv_line(name, 1e6 / tok_s["int8"],
                   f"tok_s={tok_s['int8']:.1f},pool_ratio={bytes_ratio:.2f}x,"
                   f"xchg_ratio={xratio:.2f}x,mismatches={mismatches},"
                   f"new_execs={new_execs}"))
    print(f"# int8 paged pool: {bytes_ratio:.2f}x resident tokens per pool "
          f"byte vs f32 ({q8['stats']['pool_bytes']} B vs "
          f"{f32['stats']['pool_bytes']} B, same {64} pages), sync-layer "
          f"exchange {xratio:.2f}x smaller ({per_row_q8} vs {per_row_f32} "
          f"B/row at {cfg.n_kv_heads} kv heads x {cfg.head_dim})")
    if bytes_ratio < 2.0:
        print("# WARNING: pool-byte ratio below the 2x floor this repo pins")
    if xratio < 3.5:
        print("# WARNING: exchange shrink below the 3.5x floor this repo "
              "pins")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged from the f32 "
              "paged pool (greedy parity broken)")
    if new_execs:
        print(f"# WARNING: timed replay compiled {new_execs} new "
              "executable(s) — quantized churn must not recompile")
    return [{
        "name": name,
        # speedup is the PAIRED pool-bytes-per-resident-token ratio of two
        # adjacent passes over the same trace — deterministic, so
        # compare_bench.py gates on it (a drop = the quantized pool grew)
        "paired_ratio": True,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "max_slots": args.max_slots,
        "capacity": capacity,
        "page_size": 8,
        "num_pages": 64,
        "kv_quant": "int8",
        "speedup": bytes_ratio,
        "pool_bytes_f32": f32["stats"]["pool_bytes"],
        "pool_bytes_int8": q8["stats"]["pool_bytes"],
        "peak_bytes_per_resident_token_f32":
            f32["stats"]["peak_bytes_per_resident_token"],
        "peak_bytes_per_resident_token_int8":
            q8["stats"]["peak_bytes_per_resident_token"],
        "exchange_bytes_per_row_f32": per_row_f32,
        "exchange_bytes_per_row_int8": per_row_q8,
        "exchange_shrink_vs_f32": xratio,
        "admission_prefill_executables": q8["n_pref"],
        "decode_step_executables": q8["n_dec"],
        "timed_replay_new_executables": new_execs,
        "tok_s_f32_pool": tok_s["none"],
        "tok_s_int8_pool": tok_s["int8"],
        "parity_mismatches": mismatches,
    }]


def _paged_flash_pass(args):
    """Fused Pallas paged flash-decode vs the XLA gather read path — the
    PR-10 acceptance benchmark. The SAME mixed-length greedy trace is
    served by two paged pools adjacently per round: the default backend
    (page gather densifies/chunk-streams the pool before the shared
    softmax body) and ``backend='pallas'`` (ONE kernel per pooled step:
    in-kernel page loads through the scalar-prefetched table, split-KV
    stats, kernels/flash_decode.py). Pinned:

    * ``parity_mismatches``: greedy tokens must match the gather pool
      EXACTLY — split-KV softmax agrees to f32 rounding, below the
      trace's greedy decision margins.
    * ``decode_step_executables``: ONE fused decode executable across
      admission/retirement churn (page tables stay traced data through
      the scalar-prefetch operand).
    * ``speedup`` (paired, CI-gated): fused-over-gather aggregate tok/s,
      median of adjacent rounds. On CPU the kernel body runs under the
      Pallas INTERPRETER, so the honest committed ratio is below 1x —
      the gate holds the ratio from regressing further (e.g. the fused
      route silently densifying the pool, which the jaxpr audit also
      bans statically); the compiled-kernel speedup is a TPU number.
    """
    cfg = bench_config(n_layers=4)
    fed = FedAttnConfig(n_participants=4, sync_interval=2)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(19)
    n_req = min(args.requests, 8)  # interpret mode: keep the trace bounded
    proto = poisson_trace(rng, 1, vocab_size=cfg.vocab_size, max_len=8,
                          max_new=2, rate_per_s=1e9)[0][0]
    reqs = []
    for _ in range(n_req):  # greedy: parity is exact-match
        L = int(rng.integers(12, 41))
        reqs.append(type(proto)(
            tokens=jax.numpy.asarray(
                rng.integers(3, cfg.vocab_size, size=(L,)), jax.numpy.int32),
            n_new=int(rng.integers(6, 13)),
        ))
    total_new = sum(r.n_new for r in reqs)
    capacity = 64

    pools = {}
    for backend in ("gather", "pallas"):
        eng = FedAttnEngine(
            cfg, params, fedattn=fed,
            backend=None if backend == "gather" else backend,
        )
        sched = ContinuousBatchingScheduler(
            eng, max_slots=args.max_slots, capacity=capacity,
            steps_per_admit=args.steps_per_admit,
            kv_layout="paged", page_size=8,
        )
        res = sched.run(reqs)  # warmup: compiles every pool executable
        pools[backend] = {"sched": sched, "res": res}

    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(pools["pallas"]["res"], pools["gather"]["res"])
    )
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        pools["gather"]["sched"].run(reqs)
        w_gather = time.perf_counter() - t0
        t0 = time.perf_counter()
        pools["pallas"]["sched"].run(reqs)
        w_fused = time.perf_counter() - t0
        rounds.append((w_gather / w_fused, w_gather, w_fused))
    rounds.sort()
    speedup, wall_gather, wall_fused = rounds[len(rounds) // 2]
    tok_s = {"gather": total_new / wall_gather, "fused": total_new / wall_fused}
    n_decode = pools["pallas"]["sched"].compile_counts["decode_step"]
    interpret = pallas_interpret()
    name = "serving_paged_flash"
    print(csv_line(name, 1e6 / tok_s["fused"],
                   f"tok_s={tok_s['fused']:.1f},vs_gather={speedup:.2f}x,"
                   f"interpret={int(interpret)},decode_execs={n_decode},"
                   f"mismatches={mismatches}"))
    print(f"# fused paged flash-decode: {speedup:.2f}x the gather pool "
          f"tok/s ({'interpreter' if interpret else 'compiled kernel'}; "
          f"{len(reqs)} requests, {total_new} tokens, pool "
          f"{args.max_slots}x{capacity} @ page_size 8)")
    if interpret and speedup > 1.0:
        print("# NOTE: interpret-mode fused pass outran the gather pool — "
              "machine noise, treat with suspicion")
    if n_decode != 1:
        print(f"# WARNING: fused decode_step executables = {n_decode} "
              "(expected 1 — page-table churn must not recompile)")
    if mismatches:
        print(f"# WARNING: {mismatches} requests diverged from the gather "
              "pool (greedy parity broken)")
    return [{
        "name": name,
        # speedup is a PAIRED within-run ratio (adjacent passes, median
        # round) — compare_bench.py gates on it. Interpret-mode CPU runs
        # commit an honest sub-1x baseline; the gate catches the fused
        # route regressing (e.g. silently densifying the pool).
        "paired_ratio": True,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "max_slots": args.max_slots,
        "steps_per_admit": args.steps_per_admit,
        "capacity": capacity,
        "page_size": 8,
        "interpret_mode": interpret,
        "tok_s_gather": tok_s["gather"],
        "tok_s_fused": tok_s["fused"],
        "speedup": speedup,
        "decode_step_executables": n_decode,
        "parity_mismatches": mismatches,
    }]


def _mesh_pass(cfg, fed, params, reqs, args, total_new, single_res):
    """SPMD pooled pass: same trace, KV pool capacity-sharded over an
    N-way mesh, paired adjacently against a fresh single-device pool at
    the SAME (shard-divisible) capacity. Gating metrics are the executable
    count and parity; tok/s and the mesh ratio are trend/warn-only (on one
    shared CPU the 'mesh' is collective overhead with no extra FLOP/s)."""
    from repro.launch.mesh import make_serving_mesh

    n = args.mesh
    eng_mesh = FedAttnEngine(cfg, params, fedattn=fed, mesh=make_serving_mesh(n))
    eng_one = FedAttnEngine(cfg, params, fedattn=fed)
    capacity = ContinuousBatchingScheduler.capacity_for(eng_mesh, reqs)
    sched_mesh = ContinuousBatchingScheduler(
        eng_mesh, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=args.steps_per_admit,
    )
    sched_one = ContinuousBatchingScheduler(
        eng_one, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=args.steps_per_admit,
    )
    sched_one.run(reqs)  # warmups
    mesh_res = sched_mesh.run(reqs)
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        sched_one.run(reqs)
        w_one = time.perf_counter() - t0
        t0 = time.perf_counter()
        sched_mesh.run(reqs)
        w_mesh = time.perf_counter() - t0
        rounds.append((w_mesh / w_one, w_one, w_mesh))
    rounds.sort()
    _, wall_one, wall_mesh = rounds[len(rounds) // 2]
    tok_s_one = total_new / wall_one
    tok_s_mesh = total_new / wall_mesh
    n_exec = sched_mesh.compile_counts["decode_step"]
    mismatches = sum(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(mesh_res, single_res)
    )
    ratio = tok_s_mesh / tok_s_one
    name = f"serving_stream_mesh{n}_N{fed.n_participants}_H{fed.sync_interval}"
    print(csv_line(name, 1e6 / tok_s_mesh,
                   f"tok_s={tok_s_mesh:.1f},vs_single_pool={ratio:.2f}x,"
                   f"shards={n},decode_execs={n_exec},"
                   f"mismatches={mismatches}"))
    print(f"# SPMD pool ({n} shards): {ratio:.2f}x the single-device pool "
          f"tok/s at capacity {capacity} (CPU collective overhead expected)")
    if n_exec != 1:
        print(f"# WARNING: mesh decode_step executables = {n_exec}")
    if mismatches:
        print(f"# WARNING: {mismatches} mesh requests diverged")
    return [{
        "name": name,
        "n_shards": n,
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "max_slots": args.max_slots,
        "steps_per_admit": args.steps_per_admit,
        "capacity": capacity,
        "tok_s_mesh": tok_s_mesh,
        "tok_s_single_pool": tok_s_one,
        "mesh_vs_single_ratio": ratio,
        "decode_step_executables": n_exec,
        "parity_mismatches": mismatches,
    }]


if __name__ == "__main__":
    main()
