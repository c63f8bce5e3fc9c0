"""Decode throughput: eager per-token Python loop vs the jitted lax.scan
fast path of FedAttnEngine, swept over participant counts and sync
intervals — plus compile-cost columns (warmup seconds, executable counts)
so the executable-cache behaviour is tracked alongside tok/s.

The FedAttn trade-off the paper studies (quality vs communication/compute,
§VI) is only meaningful if decode throughput is real — this benchmark is
the repo's tokens/sec ground truth on CPU (and the shape of the gap on
accelerators, where per-step Python dispatch hurts far more).

Prints ``name,us_per_call,derived`` CSV lines (us_per_call = per generated
token) plus a summary speedup line; ``main()`` also returns the records as
dicts so benchmarks/run.py can persist them to BENCH_serving.json.

Usage:
  PYTHONPATH=src python -m benchmarks.decode_throughput [--n-new 64]
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

B, L = 2, 64


def _throughput(engine, tokens, n_new: int, *, compile: bool, reps: int):
    """(tokens/sec, warmup seconds) over full generate() calls — the warmup
    call compiles every driver, so steady-state timing has them cached."""
    t0 = time.perf_counter()
    engine.generate(tokens, n_new, compile=compile)  # warmup / compile
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        engine.generate(tokens, n_new, compile=compile)
    dt = (time.perf_counter() - t0) / reps
    return n_new * B / dt, warmup_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-new", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--eager-reps", type=int, default=1)
    args, _ = ap.parse_known_args()  # tolerate benchmarks/run.py flags

    sweeps = [
        (1, 2),  # centralized baseline
        (4, 2),
        (4, 4),
        (8, 2),
    ]
    records = []
    for n_part, interval in sweeps:
        cfg = bench_config(n_layers=4)
        fed = FedAttnConfig(n_participants=n_part, sync_interval=interval)
        params = build_model(cfg).init(jax.random.key(0))
        engine = FedAttnEngine(cfg, params, fedattn=fed)
        tokens = jax.random.randint(
            jax.random.key(1), (B, L), 0, cfg.vocab_size
        )
        tps_jit, warmup_s = _throughput(
            engine, tokens, args.n_new, compile=True, reps=args.reps
        )
        n_execs = dict(engine.compile_counts)
        tps_eager, _ = _throughput(
            engine, tokens, args.n_new, compile=False, reps=args.eager_reps
        )
        speedup = tps_jit / tps_eager
        name = f"decode_N{n_part}_H{interval}"
        print(csv_line(f"{name}_eager", 1e6 / tps_eager,
                       f"tok_s={tps_eager:.1f}"))
        print(csv_line(f"{name}_jit", 1e6 / tps_jit,
                       f"tok_s={tps_jit:.1f},speedup={speedup:.1f}x,"
                       f"warmup_s={warmup_s:.2f},"
                       f"execs=p{n_execs['prefill']}+d{n_execs['decode']}"))
        records.append({
            "name": name,
            "n_new": args.n_new,
            "scan_plan": (engine._plan.period, engine._plan.n_periods),
            "tok_s_eager": tps_eager,
            "tok_s_jit": tps_jit,
            "speedup": speedup,
            "warmup_s": warmup_s,
            "prefill_executables": n_execs["prefill"],
            "decode_executables": n_execs["decode"],
        })
    speedups = [r["speedup"] for r in records]
    print(f"# jitted decode speedup over eager: min {min(speedups):.1f}x, "
          f"max {max(speedups):.1f}x at n_new={args.n_new}")
    if min(speedups) < 3.0:
        print("# WARNING: speedup below the 3x floor this repo pins")
    return records


if __name__ == "__main__":
    main()
