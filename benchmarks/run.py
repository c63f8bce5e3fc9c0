"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus ``#``
commentary validating the paper's claims (EXPERIMENTS.md §Paper-claims
records the canonical run).

Serving benchmarks (decode_throughput, prefill_throughput) additionally
return machine-readable records; these are persisted to BENCH_serving.json
(repo root by default, ``--json`` overrides) so the repo's serving-perf
trajectory — tok/s, prefill latency, compile seconds, executable counts —
is tracked across PRs instead of living only in printed CSV.

Usage:
  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only fig5 fig10
  PYTHONPATH=src python -m benchmarks.run --only decode_throughput prefill
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import platform
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

# modules whose main() returns serving-perf records for BENCH_serving.json
SERVING_MODULES = (
    "decode_throughput", "prefill_throughput", "serving_throughput"
)

MODULES = [
    ("comm_cost", "comm-cost model (SVII-A3)"),
    ("kernel_bench", "kernel microbenchmarks"),
    ("decode_throughput", "engine decode tokens/sec: eager vs jitted"),
    ("prefill_throughput", "engine prefill latency: eager vs jitted+bucketed"),
    ("serving_throughput", "continuous batching vs sequential generate"),
    ("fig5_quality_vs_h", "Fig.5 quality vs H + comm"),
    ("fig6_quality_vs_n", "Fig.6 quality vs N + compute"),
    ("fig7_sync_schedules", "Fig.7 sync schemes"),
    ("fig8_publisher_sync", "Fig.8 publisher sync frequency"),
    ("fig9_sparse_local", "Fig.9 sparse local attention"),
    ("fig10_sparse_kv", "Fig.10 sparse KV exchange"),
    ("error_propagation", "Thm.1/2 error propagation"),
    ("roofline_table", "roofline terms per (arch x shape)"),
]


def _env() -> dict:
    import jax

    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
    }


def _write_serving_json(path: pathlib.Path, results: dict) -> None:
    # a partial --only run must not drop the other modules' records — merge
    # into the existing file so the committed trajectory stays complete.
    # Environment metadata lives per module entry (not top-level) so merged
    # stale records keep the environment they were measured on.
    merged: dict = {}
    if path.exists():
        try:
            prev = json.loads(path.read_text())
            if prev.get("schema") == 1:
                merged = prev.get("results", {})
        except (json.JSONDecodeError, OSError):
            pass
    env = _env()
    merged.update(
        {mod: {"env": env, "records": recs} for mod, recs in results.items()}
    )
    doc = {
        "schema": 1,
        "generated_by": "benchmarks/run.py",
        "results": merged,
    }
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {path}")


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument(
        "--json", type=pathlib.Path,
        default=pathlib.Path(__file__).resolve().parents[1] / "BENCH_serving.json",
        help="where to write the serving-perf records (BENCH_serving.json)",
    )
    # parse_known_args: module-specific flags (e.g. serving_throughput's
    # --mesh) pass through to the modules' own parse_known_args
    args, _ = ap.parse_known_args()

    failures = []
    serving: dict = {}
    print("name,us_per_call,derived")
    for mod_name, desc in MODULES:
        if args.only and not any(o in mod_name for o in args.only):
            continue
        print(f"# === {mod_name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            records = mod.main()
            if mod_name in SERVING_MODULES and records is not None:
                serving[mod_name] = records
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(mod_name)
            print(f"# {mod_name} FAILED: {e}")
            traceback.print_exc(limit=4)
    if serving:
        _write_serving_json(args.json, serving)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
