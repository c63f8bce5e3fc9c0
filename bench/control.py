"""The readings that a cell's limits are set from. Run on the chip:

    python3 bench/control.py --workload <cell> --seeds 101,102,103 --seconds 8

One process builds the cell's engine once. For each seed it draws the
weights anew, serves the cell's traffic through a fresh scheduler for a
short window at the cell's own load, and reads the sample that a benchmark
run of that seed compares (``bench/check.py``) twice:

* the program: its served tokens against the reference (the lower
  readings: the limits must sit above the largest of them);
* the control: the reference computed with fp8 inputs to every weight
  product, at the same prompts and positions (the upper readings: the
  limits must sit below the smallest of them).

Prints one JSON line per seed, then one with the largest program reading
and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check, harness, model  # noqa: E402


def readings(workload: str, seeds: list[int], seconds: float, *, root: Path = harness.ROOT,
             require_tpu: bool = True, fault=None, out=print) -> dict:
    _, entry, cell, cfg = harness.load_cell(workload, root)
    harness.devices(entry["chips"], require_tpu)
    harness.enable_cache()
    dm, engine = harness.new_engine(cell, cfg)
    gen = importlib.import_module(f"bench.traffic.{cell['arrivals']}")
    lower = {k: 0.0 for k in check.NAMES}
    upper = {k: float("inf") for k in check.NAMES}
    for i, seed in enumerate(seeds):
        w_key, rng = model.seeds(seed)
        weights = model.make_weights(dm, w_key)
        engine.set_params(model.program_params(weights, dm))
        sched = harness.new_scheduler(engine, cell)
        traffic = gen.Traffic(cell, rng, dm.vocab, seconds)
        if i == 0:
            harness.warm_up(sched, engine, traffic.sizes, cell["warm_width"],
                            np.random.default_rng([seed, 1]), dm.vocab)
        sv = harness.serve(sched, traffic, cell, seconds, fault=fault)
        rec = harness.Record(
            dm=dm, steps_per_admit=cell["steps_per_admit"], max_slots=cell["max_slots"],
            setup_s=0.0, t0=sv.t0, t1=sv.t1, ticks=[], reqs=sv.drv.reqs, peak={},
        )
        picked = check.sample(rec.window_reqs(), np.random.default_rng([seed, 2]), cell["check"]["requests"])
        sched.cache = None
        engine.set_params(None)
        del sched, sv, rec
        gc.collect()
        t = time.perf_counter()
        prog = check.readings(weights, dm, picked)
        ctl = check.readings(weights, dm, picked, control=True)
        out(json.dumps({"seed": seed, "program": prog, "control": ctl,
                        "reference_s": time.perf_counter() - t}))
        for k in check.NAMES:
            lower[k] = max(lower[k], prog[k])
            upper[k] = min(upper[k], ctl[k])
        del weights
        gc.collect()
    summary = {"workload": workload, "seeds": len(seeds), "lower": lower, "upper": upper}
    out(json.dumps(summary))
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    try:
        readings(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds)
    except harness.Refused as e:
        raise SystemExit(f"control: refused: {e}")


if __name__ == "__main__":
    main()
