"""Trace one cell's window and put its time down to the program's layers.

    python3 bench/profile_cell.py --workload <cell> --seed <n> --seconds <s> [--keep <file>]

from the root of a checkout, on the chip. It builds, warms up, serves and
traces the cell as a ``--trace 1`` run of ``bench/run.py`` does
(``harness.measure``), but with JAX's persistent compilation cache off, so
``setup_s`` is a cold compile's, and prints one JSON object: the trace
reduced by ``bench/layers.py`` (device time per entry point and per model
scope, idle time by innermost host span), each scope's share of the
``decode_step`` module, its ops outside the layers, the cell's
``per_layer`` metrics as the benchmark reads them, the counters' change
and the window's steps. ``--keep`` copies the ``.xplane.pb`` there. It
checks nothing against the reference: it is the measurement behind
``PERF.md``'s breakdowns.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, layers  # noqa: E402
from repro.models.scopes import LAYER_SCAN  # noqa: E402


def profile(workload: str, seed: int, seconds: float, keep=None, *,
            root: Path = ROOT, require_tpu: bool = True, log=print) -> dict:
    """The printed object; without a TPU trace ``layers`` is None."""
    m = harness.measure(workload, seed, seconds, True, t_process=T_PROCESS, root=root,
                        require_tpu=require_tpu, keep=keep, log=log)
    rec, s = m.rec, m.rec.trace
    step = rec.decode_step_s()
    out = {
        "device": {"kind": m.devs[0].device_kind, "memory_peak_bytes": m.memory_peak_bytes},
        "setup_s": rec.setup_s,
        "window": {"steps": len(rec.ticks), "admitting": sum(bool(t.admitted) for t in rec.ticks),
                   "tokens": sum(t.tokens for t in rec.ticks),
                   "plain_step_ms_per_substep": None if step is None else step * 1e3,
                   "compiles": m.compiles},
        "counters": rec.counters,
        "metrics": {spec["name"]: harness.metric_reader(spec["name"])(rec)
                    for spec in harness.metrics_for(m.bench, workload, "per_layer")},
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
