"""Serving launcher — collaborative FedAttn inference.

By default the model is the reduced smoke-size variant of ``--arch``;
``--n-layers N`` serves the published config instead, at its published
widths and dtype with only its depth cut to N layers. Weights are random,
drawn from ``--seed``.

Demonstrates the paper's deployment story end to end: N participants hold
private token segments; the engine runs FedAttn prefill (periodic KV
exchange per the schedule) and the publisher decodes the answer.

Both phases run compiled by default: a jitted shape-bucketed prefill plus
the jitted lax.scan decode driver (scan-over-layers when the sync schedule
is periodic). Pass ``--no-compile`` to run the eager per-token reference
loops instead (same numbers, ~30x slower decode on CPU — see
benchmarks/decode_throughput.py and benchmarks/prefill_throughput.py).

Bucket policy: with ``--bucket pow2`` (default) the request length and
n-new are padded up to power-of-two buckets so mixed request lengths share
one compiled executable per bucket — steady-state serving never
recompiles. ``--bucket none`` compiles per exact shape (more executables,
no padded FLOPs).

Streaming mode: ``--stream`` feeds a Poisson arrival trace of mixed-length
requests through the continuous-batching scheduler — a fixed KV slot pool
plus ONE resident decode executable serving every in-flight request, new
admissions landing mid-flight (see repro/serving/scheduler.py and
benchmarks/serving_throughput.py for the >=2x aggregate-tok/s pin vs
sequential generate calls).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --participants 4 \
      --sync-interval 2 --kv-ratio 0.5 --n-new 16
  PYTHONPATH=src python -m repro.launch.serve --stream --stream-requests 16 \
      --arrival-rate 4 --max-slots 8
  PYTHONPATH=src python -m repro.launch.serve --n-layers 8 --stream \
      --sync-interval 4 --seq-len 2048 --n-new 32  # TPU
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ASSIGNED_ARCHS, get_config, get_reduced_config
from repro.serving import FedAttnEngine, Request
from repro.types import FedAttnConfig, ModelConfig


def build_config(arch: str, n_layers: Optional[int] = None) -> ModelConfig:
    """The model ``serve`` runs: the published config of ``arch`` with only
    its depth cut to ``n_layers`` (widths, vocab and dtype as published),
    or — ``n_layers=None`` — its reduced smoke-size variant."""
    if n_layers is None:
        return get_reduced_config(arch)
    return get_config(arch).replace(n_layers=n_layers)


def poisson_trace(
    rng: np.random.Generator,
    n_requests: int,
    *,
    vocab_size: int,
    max_len: int,
    max_new: int,
    rate_per_s: float,
    temperature: float = 0.0,
) -> tuple[list[Request], list[float]]:
    """Mixed-length request trace with exponential inter-arrival gaps —
    the workload shape serving papers benchmark against. Shared by the
    --stream demo and benchmarks/serving_throughput.py."""
    reqs, arrivals, t = [], [], 0.0
    for i in range(n_requests):
        L = int(rng.integers(max(4, max_len // 4), max_len + 1))
        n_new = int(rng.integers(max(2, max_new // 4), max_new + 1))
        toks = rng.integers(3, vocab_size, size=(L,))
        sample = temperature > 0.0
        reqs.append(
            Request(
                tokens=jax.numpy.asarray(toks, jax.numpy.int32),
                n_new=n_new,
                temperature=temperature,
                rng=jax.random.key(1000 + i) if sample else None,
            )
        )
        arrivals.append(t)
        t += float(rng.exponential(1.0 / rate_per_s))
    return reqs, arrivals


def run_stream(engine: FedAttnEngine, config, args) -> None:
    from repro.serving.scheduler import ContinuousBatchingScheduler

    rng = np.random.default_rng(args.seed)
    reqs, arrivals = poisson_trace(
        rng, args.stream_requests,
        vocab_size=config.vocab_size, max_len=args.seq_len,
        max_new=args.n_new, rate_per_s=args.arrival_rate,
    )
    capacity = ContinuousBatchingScheduler.capacity_for(engine, reqs)
    # spec_k > 0 replaces the fused multi-step scan with draft+verify
    # ticks (the scheduler requires steps_per_admit == 1 there)
    steps_per_admit = 1 if args.spec_k > 0 else args.steps_per_admit
    sched = ContinuousBatchingScheduler(
        engine, max_slots=args.max_slots, capacity=capacity,
        steps_per_admit=steps_per_admit,
        prefix_cache=args.prefix_cache,
        spec_k=args.spec_k,
    )
    # warmup: compile the pool executables the timed run will hit, so it
    # measures steady-state serving, not compile time. Admission coalescing
    # keys prefill executables on the (pow2) group width too, so one
    # representative per bucket is not enough: replay the whole trace once
    # with every request queued (widest groups per bucket) and once at the
    # real arrival pattern (the widths backlog drains actually form).
    sched.run(reqs)
    sched.run(reqs, arrival_times=arrivals)
    sched.latency_stats(reset=True)  # timed pass gets its own percentiles
    t0 = time.perf_counter()
    results = sched.run(reqs, arrival_times=arrivals)
    wall = time.perf_counter() - t0
    total = sum(r.tokens.shape[1] for r in results)
    shards = (
        engine.spmd.mesh.shape["model"] if engine.spmd is not None else 1
    )
    print(f"stream: {len(reqs)} requests (Poisson rate {args.arrival_rate}/s), "
          f"pool {args.max_slots} slots x {capacity} pages"
          + (f" sharded over {shards} devices" if shards > 1 else "")
          + f", steps_per_admit={steps_per_admit}"
          + (f", spec_k={args.spec_k}" if args.spec_k else ""))
    st = sched.pool_stats()
    prefix = ""
    if args.prefix_cache:
        hits, misses = st["prefix_hits"], st["prefix_misses"]
        rate = hits / max(1, hits + misses)
        prefix = (f", prefix hit-rate {rate:.0%} "
                  f"({st['prefix_tokens_reused']} prompt tokens reused)")
    print(f"aggregate decode throughput: {total / wall:,.1f} tok/s "
          f"({total} tokens / {wall:.2f}s wall incl. arrivals){prefix}")
    if st.get("tpot_n"):
        # the per-request view — what speculative decoding moves: each
        # request's own tokens per second (1/TPOT), not the pool total
        print(f"per-request latency: ttft p50 {st['ttft_p50'] * 1e3:.1f} ms / "
              f"p95 {st['ttft_p95'] * 1e3:.1f} ms; tpot p50 "
              f"{st['tpot_p50'] * 1e3:.2f} ms/tok / p95 "
              f"{st['tpot_p95'] * 1e3:.2f} ms/tok "
              f"(p50 per-request {1.0 / st['tpot_p50']:,.1f} tok/s)")
    if args.spec_k:
        print(f"speculation: acceptance rate "
              f"{st['spec_acceptance_rate']:.0%} "
              f"({sched.stats['spec_accepted']}/{sched.stats['spec_drafted']} "
              f"draft tokens over {sched.stats['verify_ticks']} verify ticks)")
    print(f"executables: {sched.compile_counts} "
          f"({'verify' if args.spec_k else 'decode'}_step stays 1 — "
          f"admission/retirement never recompiles)")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ASSIGNED_ARCHS), default="qwen2-7b")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="serve the published config at its published "
                         "widths with only its depth cut to N layers "
                         "(default: the reduced smoke-size variant)")
    ap.add_argument("--participants", type=int, default=4)
    ap.add_argument("--sync-interval", type=int, default=2)
    ap.add_argument("--schedule", default="uniform")
    ap.add_argument("--kv-ratio", type=float, default=1.0)
    ap.add_argument("--kv-selection", default="random")
    ap.add_argument("--kv-quant", choices=["none", "int8", "fp8"],
                    default="none",
                    help="quantized KV (serving/quant.py): the paged pool "
                         "stores int8/fp8 codes + per-page-per-head scales "
                         "(~4x/2x residents per pool byte vs f32/bf16) and "
                         "sync-layer exchange ships compressed rows "
                         "(~3.6x smaller at dh=32); greedy tokens stay "
                         "parity-exact, logprobs drift within ~1e-3 "
                         "(attention-only stacks)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--n-new", type=int, default=8)
    ap.add_argument("--no-compile", action="store_true",
                    help="eager per-token decode + per-layer prefill "
                         "(reference path)")
    ap.add_argument("--bucket", choices=["pow2", "none"], default="pow2",
                    help="executable-sharing policy: 'pow2' pads L and "
                         "n-new up to power-of-two buckets so mixed request "
                         "lengths reuse one compiled executable per bucket; "
                         "'none' compiles per exact shape")
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching mode: feed a Poisson arrival "
                         "trace of mixed-length requests through the KV "
                         "slot-pool scheduler instead of one batched "
                         "generate call")
    ap.add_argument("--stream-requests", type=int, default=16,
                    help="number of requests in the --stream trace")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="--stream Poisson arrival rate (requests/sec)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="--stream KV pool slots (max concurrent requests)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="--stream: enable the refcounted prefix cache on "
                         "the paged KV pool — requests sharing a cached "
                         "prompt map its pages copy-free and prefill only "
                         "their suffix (attention-only stacks); the hit "
                         "rate is reported next to tok/s")
    ap.add_argument("--steps-per-admit", type=int, default=4,
                    help="--stream decode sub-steps fused per scheduler "
                         "tick (amortizes dispatch; admission latency "
                         "grows by the same factor)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="--stream speculative decoding: draft K candidate "
                         "tokens per slot per tick (n-gram prompt+output "
                         "lookup drafter) and verify them in ONE "
                         "multi-token forward — per-request latency drops "
                         "by the acceptance rate at exact token/logprob "
                         "parity (attention-only stacks; forces "
                         "steps_per_admit=1)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="--stream SPMD mode: shard the KV slot pool's "
                         "capacity dim over an N-way 'model' mesh and run "
                         "the resident decode step as flash-decoding "
                         "(partial softmax per shard + one psum). Needs N "
                         "devices — on CPU set XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N before launching")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    config = build_config(args.arch, args.n_layers)
    if config.is_encoder_decoder:
        raise SystemExit("decoder-only serving demo; enc-dec covered in examples")
    fed = FedAttnConfig(
        n_participants=args.participants,
        sync_interval=args.sync_interval,
        schedule=args.schedule,
        kv_exchange_ratio=args.kv_ratio,
        kv_selection=args.kv_selection,
        kv_quant=args.kv_quant,
    )
    mesh = None
    if args.mesh:
        if not args.stream:
            raise SystemExit("--mesh applies to the --stream pooled path")
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
    engine = FedAttnEngine(config, fedattn=fed, bucket=args.bucket, mesh=mesh)
    engine.init_random_params(args.seed)
    if args.no_compile and "stacked" in engine.params:
        # the eager reference reads loop-form weights: unstack the same draw
        params = dict(engine.params)
        stacked = params.pop("stacked")
        n_per = jax.tree.leaves(stacked)[0].shape[0]
        params["layers"] = [jax.tree.map(lambda x: x[i], layer)
                            for i in range(n_per) for layer in stacked]
        params["layers"] += params.pop("remainder")
        engine.set_params(params)

    if args.stream:
        run_stream(engine, config, args)
        return

    tokens = jax.random.randint(
        jax.random.key(1), (args.batch, args.seq_len), 3, config.vocab_size
    )
    extra = None
    if config.frontend == "vision":
        from repro.models.frontend import fake_vision_embeds

        extra = fake_vision_embeds(
            jax.random.key(2), args.batch, config.frontend_tokens, config.d_model
        )
    compile_decode = not args.no_compile
    t_compile = 0.0
    if compile_decode:
        # warmup: compile the prefill + decode drivers so the timed call
        # below measures steady state (eager mode has no compile step)
        t0 = time.perf_counter()
        engine.generate(
            tokens, args.n_new, rng=jax.random.key(3), extra_embeds=extra,
        )
        t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = engine.generate(
        tokens, args.n_new, rng=jax.random.key(3), extra_embeds=extra,
        compile=compile_decode,
    )
    dt = time.perf_counter() - t0
    print(f"arch={config.name} N={args.participants} H={args.sync_interval} "
          f"schedule={args.schedule} kv_ratio={args.kv_ratio} "
          f"drivers={'jit' if compile_decode else 'eager'} "
          f"bucket={args.bucket} scan={engine._plan.period}x"
          f"{engine._plan.n_periods}")
    print("generated tokens:\n", res.tokens)
    print("mean token logprob:", float(res.logprobs.mean()))
    print(f"decode throughput: {args.n_new * args.batch / dt:,.1f} tok/s "
          f"(batch x n_new / wall, prefill included)")
    if compile_decode:
        print(f"warmup (compile) time: {t_compile:.2f}s; compiled drivers: "
              f"{engine.compile_counts}")
    print(f"prefill KV upload per participant: {res.prefill_comm_bytes:,.0f} bytes")


if __name__ == "__main__":
    main()
