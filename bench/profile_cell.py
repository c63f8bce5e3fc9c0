"""Trace one cell's window and put its time down to the program's layers.

    python3 bench/profile_cell.py --workload <cell> --seed <n> --seconds <s> [--keep <file>]

from the root of a checkout, on the chip. It builds, warms up and serves
the cell as ``bench/run.py`` does (the functions of ``bench/harness.py``,
but with JAX's persistent compilation cache off, so ``setup_s`` is a cold
compile's), traces the window, and prints one JSON object: the trace reduced by
``bench/layers.py`` (device time per entry point and per model scope, idle
time by innermost host span), the numbers those times give
(``decode_device_ms``, ``decode_attn_pct``, ``prefill_ms_per_ktok``,
``admit_pad_pct``, ``step_host_ms``), and the window's step walls.
``--keep`` copies the ``.xplane.pb`` there. It checks nothing against the
reference and reports no benchmark metric: it is the measurement behind
``PERF.md``'s breakdowns.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, layers, model  # noqa: E402
from repro.models.scopes import LAYER_SCAN  # noqa: E402


def profile(workload: str, seed: int, seconds: float, keep=None, *,
            root: Path = ROOT, require_tpu: bool = True, log=print) -> dict:
    """The printed object; without a TPU trace ``layers`` is None."""
    bench, entry, cell, cfg = harness.load_cell(workload, root)
    dev = harness.devices(entry["chips"], require_tpu)[0]
    log(f"device: {dev.platform} {dev.device_kind}")
    w_key, rng = model.seeds(seed)
    dm, engine = harness.new_engine(cell, cfg)
    engine.set_params(model.program_params(model.make_weights(dm, w_key)))
    sched = harness.new_scheduler(engine, cell)
    gen = importlib.import_module(f"bench.traffic.{cell['arrivals']}")
    traffic = gen.Traffic(cell, rng, dm.vocab, seconds)
    harness.warm_up(sched, engine, traffic.sizes, cell["warm_width"],
                    np.random.default_rng([seed, 1]), dm.vocab)

    at_start: dict = {}
    tracer = tempfile.mkdtemp(prefix="bench-profile-")
    # serve() hands the scheduler to its hook just before the window opens
    sv = harness.serve(sched, traffic, cell, seconds, trace_dir=tracer,
                       fault=lambda s: at_start.update(s.stats))
    at_end = dict(sched.stats)
    (path,) = glob.glob(f"{tracer}/plugins/profile/*/*.xplane.pb")
    if keep:
        shutil.copy(path, keep)
    s = layers.summarize(layers.read(path))
    shutil.rmtree(tracer, ignore_errors=True)

    ticks = [k for k in sv.drv.ticks if k.phase == "window"]
    plain = [k.t_end - k.t_start for k in ticks if not k.admitted]
    k = cell["steps_per_admit"]
    out = {
        "device": {"kind": dev.device_kind, "memory_peak_bytes":
                   (dev.memory_stats() or {}).get("peak_bytes_in_use")},
        "setup_s": sv.t0 - T_PROCESS,
        "window": {"steps": len(ticks), "admitting": sum(bool(t.admitted) for t in ticks),
                   "tokens": sum(t.tokens for t in ticks),
                   "plain_step_ms_per_substep": (sum(plain) / len(plain) / k * 1e3
                                                 if plain else None),
                   "compiles": sv.compiles},
        "counters": {key: at_end[key] - at_start[key] for key in at_end},
    }
    if s is None:
        out["layers"] = None
        return out
    out["layers"] = layers.as_dict(s)
    out["decode_step_scopes_pct"] = {
        scope: layers.scope_pct(s, "decode_step", scope)
        for (e, scope) in s.scope_s if e == "decode_step"}
    outside = [(op, t) for op, t in s.op_s.items() if op.startswith("decode_step/") and
               op.split("/")[1] in (layers.UNSCOPED, layers.COMPILER, LAYER_SCAN)]
    out["decode_step_outside_layers"] = sorted(outside, key=lambda kv: -kv[1])[:20]
    out["metrics"] = {
        "decode_device_ms": layers.decode_device_ms(s, k),
        "decode_attn_pct": layers.decode_attn_pct(s),
        "prefill_ms_per_ktok": layers.prefill_ms_per_ktok(
            s, at_end["prefill_tokens"] - at_start["prefill_tokens"]),
        "admit_pad_pct": layers.admit_pad_pct(at_start, at_end),
        "step_host_ms": layers.step_host_ms(s),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Trace one cell's window, by program layer.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the trace's .xplane.pb to this file")
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    import jax

    # Everything compiles in this process, with no persistent cache: JAX
    # keys cached executables without their debug metadata, so one compiled
    # from an earlier tree would name its ops by that tree's scopes.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        out = profile(args.workload, args.seed, args.seconds, args.keep, log=log)
    except harness.Refused as e:
        log(f"profile: refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
