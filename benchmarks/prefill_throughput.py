"""Prefill latency: eager per-layer Python loop vs the jitted shape-bucketed
prefill of FedAttnEngine, on a steady stream of MIXED request lengths.

This is the serving scenario the bucketed executable cache exists for: real
traffic never arrives at one length, so a per-exact-shape compile pays a
fresh XLA compilation for every new L, while the pow2 bucket policy pads
requests into a shared bucket and reuses one executable. The benchmark
pins both effects:

  * steady-state latency — jitted+bucketed must be >= 5x faster than the
    eager per-layer loop (the acceptance floor; tests/test_perf_regression
    pins a conservative 2x),
  * recompile count — the whole mixed-length sweep must compile exactly ONE
    prefill executable per bucket (reported per point).

Prints ``name,us_per_call,derived`` CSV lines; ``main()`` also returns the
records as dicts so benchmarks/run.py can persist them to
BENCH_serving.json.

Usage:
  PYTHONPATH=src python -m benchmarks.prefill_throughput [--reps 5]
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from common import bench_config, csv_line  # noqa: E402

from repro.models import build_model  # noqa: E402
from repro.serving import FedAttnEngine  # noqa: E402
from repro.types import FedAttnConfig  # noqa: E402

B = 2
# mixed request lengths, all inside the 64-bucket
LENGTHS = (33, 40, 48, 57, 64)


def _requests(cfg, lengths):
    return [
        jax.random.randint(jax.random.key(10 + i), (B, L), 0, cfg.vocab_size)
        for i, L in enumerate(lengths)
    ]


def _sweep(engine, reqs, *, compile: bool, reps: int) -> float:
    """Mean seconds per request over the whole mixed-length stream."""
    for r in reqs:  # warmup / compile every bucket member once
        engine.generate(r, 1, compile=compile)
    t0 = time.perf_counter()
    for _ in range(reps):
        for r in reqs:
            engine.generate(r, 1, compile=compile)
    return (time.perf_counter() - t0) / (reps * len(reqs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--eager-reps", type=int, default=2)
    args, _ = ap.parse_known_args()  # tolerate benchmarks/run.py flags

    records = []
    for n_part, interval in [(1, 2), (4, 2), (8, 2)]:
        cfg = bench_config(n_layers=8)
        fed = FedAttnConfig(n_participants=n_part, sync_interval=interval)
        params = build_model(cfg).init(jax.random.key(0))
        reqs = _requests(cfg, LENGTHS)

        eng = FedAttnEngine(cfg, params, fedattn=fed, bucket="pow2")
        t0 = time.perf_counter()
        eng.generate(reqs[0], 1)  # warmup: compiles the (one) bucket executable
        warmup_s = time.perf_counter() - t0
        dt_jit = _sweep(eng, reqs, compile=True, reps=args.reps)

        eng_eager = FedAttnEngine(cfg, params, fedattn=fed)
        dt_eager = _sweep(eng_eager, reqs, compile=False, reps=args.eager_reps)

        speedup = dt_eager / dt_jit
        n_prefill = eng.compile_counts["prefill"]
        name = f"prefill_N{n_part}_H{interval}"
        print(csv_line(f"{name}_eager", dt_eager * 1e6,
                       f"ms_per_req={dt_eager*1e3:.2f}"))
        print(csv_line(f"{name}_jit", dt_jit * 1e6,
                       f"ms_per_req={dt_jit*1e3:.2f},speedup={speedup:.1f}x,"
                       f"prefill_execs={n_prefill},warmup_s={warmup_s:.2f}"))
        records.append({
            "name": name,
            "lengths": list(LENGTHS),
            "scan_plan": (eng._plan.period, eng._plan.n_periods),
            "prefill_ms_eager": dt_eager * 1e3,
            "prefill_ms_jit": dt_jit * 1e3,
            "speedup": speedup,
            "warmup_s": warmup_s,
            "prefill_executables": n_prefill,
        })
        assert n_prefill == 1, (
            f"bucketed prefill recompiled: {n_prefill} executables for "
            f"lengths {LENGTHS}"
        )
    floor = min(r["speedup"] for r in records)
    print(f"# jitted+bucketed prefill speedup over eager: min {floor:.1f}x "
          f"across mixed lengths {LENGTHS} (one executable per sweep point)")
    if floor < 5.0:
        print("# WARNING: speedup below the 5x acceptance floor")
    return records


if __name__ == "__main__":
    main()
