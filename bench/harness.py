"""One run of one cell: build, warm up, measure, check, report.

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``), whose ``model_type`` names its
architecture (``bench/arch/<model_type>.py``); its own file
``bench/cells/<cell>.json`` holds the traffic's parameters and the
deployment's knobs, and names the arrival process, whose generator is
``bench/traffic/<arrivals>.py``. Each metric is read by
``bench/metrics/<metric>.py`` from the run's :class:`Record`: the window's
steps and requests, the change of the scheduler's counters over the
window, and in a ``--trace 1`` run the window's trace reduced to the
program's layers (``bench/layers.py``). Nothing here names a cell, a
configuration, an architecture or a metric.

The window drives ``ContinuousBatchingScheduler.step()`` over a
``FedAttnEngine``: the harness submits each request when its client sends
it and calls ``step()`` in a loop, and keeps its own clock per request from the
scheduler's public surface alone (``submit``, ``step``, ``n_active``,
``n_queued``, ``pop_result``, ``stats``):

* admission is FIFO, so the drop in ``n_queued`` across a ``step()`` names
  the requests it admitted; their first token is due at its end;
* a step emits the first token of each request it admits and then runs
  ``steps_per_admit`` decode sub-steps over every resident request, so a
  resident request gains that many tokens per step, up to its ``n_new``;
* a request's last token is at the end of the step after which
  ``pop_result`` returns it.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

from bench import check, flops, layers, model
from bench.model import Refused
from bench.workload import prompt

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass
class Tick:
    t_start: float
    t_end: float
    admitted: int  # requests this step() admitted
    resident: int  # requests its decode sub-steps served
    tokens: int  # tokens it emitted
    prefill_tokens: int  # prompt tokens it admitted
    work: list  # (prompt length, tokens before, tokens after) per request
    phase: str

    def flops(self, dm: model.Dims) -> int:
        """Model FLOPs of the step's work (bench/flops.py): the prefill of
        each request it admitted, and the decode sub-steps that produced
        generated tokens ``before .. after - 1`` (token 0 is the
        prefill's)."""
        total = 0
        for L, before, after in self.work:
            if before == 0:
                total += flops.prefill_flops(dm, L)
            total += flops.decode_flops(dm, L, max(before, 1) - 1, after - 1)
        return total


@dataclass
class Record:
    """Everything a metric reader may read."""

    dm: model.Dims
    steps_per_admit: int
    max_slots: int
    setup_s: float
    t0: float  # window start
    t1: float  # window end: the end of its last step()
    ticks: list  # the window's steps
    reqs: list  # every request of the run
    peak: dict  # bench/peaks.json entry of this device
    # the scheduler's counters (``stats``): their change over the window
    counters: dict = field(default_factory=dict)
    trace: Optional[object] = None  # bench.layers.Summary of a --trace 1 run

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def decode_step_s(self) -> Optional[float]:
        """Mean wall of the window's steps that admitted nothing, per
        decode sub-step."""
        plain = [t.t_end - t.t_start for t in self.ticks if not t.admitted]
        return sum(plain) / len(plain) / self.steps_per_admit if plain else None

    def window_reqs(self) -> list:
        """The requests the window's latencies are over: those it
        completed."""
        return [r for r in self.reqs
                if r.t_last is not None and self.t0 <= r.t_last <= self.t1]


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        raise Refused(f"no reader {path} for metric {name!r}")
    return _module(path, f"bench.metrics.{name}").read


def load_cell(workload: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its entry for ``workload``, the cell file, the
    configuration file)."""
    bench = model.load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = model.load_json(root / "bench" / "cells" / f"{workload}.json")
    cfg = model.load_json(root / "bench" / "configs" / f"{entry['config']}.json")
    return bench, entry, cell, cfg


def metrics_for(bench: dict, workload: str, key: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[key] if workload in m.get("workloads", [workload])]


def devices(chips: int, require_tpu: bool):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no accelerator: {e}")
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.count = {e: 0 for e in self.EVENTS}
        self._cb = self._event
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _event(self, name, secs, **kw):
        if self.on and name in self.count:
            self.count[name] += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._cb)


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Client:
    """The benchmark's client: submits due requests, steps the scheduler,
    keeps the clocks."""

    def __init__(self, sched, traffic, steps_per_admit: int):
        self.sched = sched
        self.traffic = traffic
        self.k = steps_per_admit
        self.queue: deque = deque()  # submitted, not yet admitted (FIFO)
        self.resident: list = []
        self.reqs: list = []
        self.ticks: list = []
        self.phase = "warm"

    def submit(self, reqs: list, now: float) -> None:
        from repro.serving import Request

        for r in reqs:
            r.t_submit = now
            r.phase = self.phase
            r.rid = self.sched.submit(Request(tokens=r.tokens, n_new=r.n_new))
            self.queue.append(r)
            self.reqs.append(r)

    def tick(self) -> bool:
        """Submit what is due and make one step(); False when there was
        nothing to do (the caller waits for the next arrival)."""
        sched = self.sched
        with _span("bench.submit"):
            now = time.perf_counter()
            self.submit(self.traffic.pop_due(now), now)
        if not self.queue and not self.resident:
            return False
        admitting = bool(self.queue) and sched.n_active < sched.max_slots
        q0 = sched.n_queued
        with _span("bench.admit_step" if admitting else "bench.decode_step"):
            t_s = time.perf_counter()
            sched.step()
            t_e = time.perf_counter()
        with _span("bench.bookkeeping"):
            self._account(t_s, t_e, q0 - sched.n_queued)
        return True

    def _account(self, t_s: float, t_e: float, n_adm: int) -> None:
        admitted = [self.queue.popleft() for _ in range(n_adm)]
        for r in admitted:
            r.t_admit, r.t_first = t_s, t_e
        resident = self.resident + admitted
        tokens = 0
        work = []
        still = []
        for r in resident:
            before = r.emitted
            r.emitted = min(r.n_new, (1 + self.k) if before == 0 else before + self.k)
            tokens += r.emitted - before
            work.append((r.prompt_len, before, r.emitted))
            if r.emitted < r.n_new:
                still.append(r)
                continue
            res = self.sched.pop_result(r.rid)
            if res is None:
                raise RuntimeError(
                    f"request {r.idx}: {r.n_new} tokens are due after this step "
                    "but the scheduler holds no result for it")
            r.t_last = t_e
            r.out_tokens = np.asarray(res.tokens)[0]
            r.out_logprobs = np.asarray(res.logprobs, np.float32)[0]
            if r.out_tokens.shape[0] != r.n_new:
                raise RuntimeError(f"request {r.idx}: {r.out_tokens.shape[0]} "
                                   f"tokens served, {r.n_new} asked")
            self.traffic.completed(r, t_e)
        self.resident = still
        self.ticks.append(Tick(
            t_start=t_s, t_end=t_e, admitted=n_adm, resident=len(resident),
            tokens=tokens, prefill_tokens=sum(r.prompt_len for r in admitted),
            work=work, phase=self.phase,
        ))

    def run_until(self, t_stop: float, *, done=None) -> None:
        """Step until ``t_stop`` (no step starts after it) or ``done()``."""
        while time.perf_counter() < t_stop and not (done and done()):
            if not self.tick():
                nxt = self.traffic.next_due()
                wake = t_stop if nxt is None else min(nxt, t_stop)
                with _span("bench.wait"):
                    time.sleep(max(0.0, wake - time.perf_counter()))


def warm_up(sched, engine, sizes, width: int, rng, vocab: int) -> int:
    """Compile what the window will run, before it: every admission width
    (powers of two up to ``width``) at every prefill bucket that the cell's
    sizes fall in, the decode step, and the program's per-length work for
    every distinct prompt length of the sizes. Each group is admitted into
    the empty pool and served to its end. Returns the number of groups."""
    from repro.serving import Request

    by_bucket: dict = {}
    for L in sorted({L for L, _ in sizes}):
        by_bucket.setdefault(engine.bucket_len(L), []).append(L)
    widths = [1 << i for i in range(int(math.log2(width)) + 1)]
    n = 0
    for bucket in sorted(by_bucket):
        lens = by_bucket[bucket]
        groups, at = [], 0
        for B in widths:
            groups.append([lens[(at + i) % len(lens)] for i in range(B)])
            at += B
        groups += [lens[i: i + width] for i in range(at, len(lens), width)]
        for group in groups:
            ids = [sched.submit(Request(tokens=prompt(rng, L, vocab), n_new=2))
                   for L in group]
            while not sched.done():
                sched.step()
            for rid in ids:
                sched.pop_result(rid)
            n += 1
    return n


def _p95(xs) -> Optional[float]:
    return float(np.percentile(xs, 95)) if len(xs) else None


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's own directory
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however short its compile, so that a second run of a
    cell compiles nothing."""
    import jax

    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def new_engine(cell: dict, cfg: dict, params=None):
    """(model sizes, the program's engine) for this cell's configuration."""
    from repro.serving import FedAttnEngine

    dm = model.dims(cfg, cell["participants"], cell["sync_interval"])
    mc = model.program_config(cfg, dm)
    return dm, FedAttnEngine(mc, params, fedattn=mc.fedattn, backend=cell.get("backend"))


def new_scheduler(engine, cell: dict):
    from repro.serving import ContinuousBatchingScheduler

    return ContinuousBatchingScheduler(
        engine, max_slots=cell["max_slots"], capacity=cell["capacity"],
        steps_per_admit=cell["steps_per_admit"], page_size=cell["page_size"],
    )


@dataclass
class Served:
    drv: Client
    t0: float
    t1: float
    compiles: dict  # traces and compiles inside the window
    counters: dict  # the scheduler's counters: their change over the window


def serve(sched, traffic, cell: dict, seconds: float, *,
          fault=None, trace_dir: Optional[str] = None) -> Served:
    """The traffic's ramp and settling, then the window of ``seconds``,
    traced into ``trace_dir`` if given. ``fault`` (tests only) is called
    with the scheduler before the window."""
    import jax

    drv = Client(sched, traffic, cell["steps_per_admit"])
    drv.phase = "pre"
    drv.run_until(math.inf, done=lambda: traffic.ramped)
    drv.run_until(time.perf_counter() + cell["settle_s"])
    if fault is not None:
        fault(sched)

    counter = CompileCounter()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    at_start = dict(sched.stats)
    counter.on = True
    drv.phase = "window"
    t0 = time.perf_counter()
    with _span("bench.window"):
        drv.run_until(t0 + seconds)
        t1 = max([t0] + [k.t_end for k in drv.ticks if k.phase == "window"])
    counter.on = False
    at_end = dict(sched.stats)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    counter.close()
    return Served(drv, t0, t1, dict(counter.count),
                  {k: at_end[k] - at_start[k] for k in at_end})


def _read_trace(trace_dir: str, keep: Optional[str]):
    """The layered summary of the window's trace (None without one that
    holds a device plane); the ``.xplane.pb`` is copied to ``keep`` if
    given, and the directory removed."""
    import glob
    import shutil

    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    summary = None
    if files:
        if keep:
            shutil.copy(files[0], keep)
        summary = layers.summarize(layers.read(files[0]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


@dataclass
class Measured:
    """One served window of a cell, and what its check needs."""

    bench: dict
    cell: dict
    rec: Record
    weights: dict
    devs: list
    memory_peak_bytes: Optional[int]
    compiles: dict  # traces and compiles inside the window


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            t_process: float, root: Path = ROOT, require_tpu: bool = True,
            fault=None, keep: Optional[str] = None, log=print) -> Measured:
    """Build the cell, warm it up and serve its window; with ``trace`` the
    window is traced and reduced (``keep`` copies the trace there)."""
    bench, entry, cell, cfg = load_cell(workload, root)
    devs = devices(entry["chips"], require_tpu)
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")

    log(f"compile cache: {enable_cache()}; at {time.perf_counter() - t_process:.1f} s")
    peaks = model.load_json(BENCH / "peaks.json")
    if dev.device_kind not in peaks and require_tpu:
        raise Refused(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
    peak = peaks.get(dev.device_kind, {})

    w_key, rng = model.seeds(seed)
    dm, engine = new_engine(cell, cfg)
    weights = model.make_weights(dm, w_key)
    engine.set_params(model.program_params(weights, dm))
    sched = new_scheduler(engine, cell)
    log(f"set-up: weights and engine built at {time.perf_counter() - t_process:.1f} s")
    gen = importlib.import_module(f"bench.traffic.{cell['arrivals']}")
    traffic = gen.Traffic(cell, rng, dm.vocab, seconds)
    warm_rng = np.random.default_rng([seed, 1])
    groups = warm_up(sched, engine, traffic.sizes, cell["warm_width"], warm_rng, dm.vocab)
    log(f"warm-up: {groups} admission groups; executables {sched.compile_counts}; "
        f"done at {time.perf_counter() - t_process:.1f} s")

    tracer = None
    if trace:
        import tempfile

        tracer = tempfile.mkdtemp(prefix="bench-trace-")
    sv = serve(sched, traffic, cell, seconds, fault=fault, trace_dir=tracer)
    drv, t0, t1 = sv.drv, sv.t0, sv.t1
    setup_s = t0 - t_process

    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")

    window_ticks = [k for k in drv.ticks if k.phase == "window"]
    rec = Record(
        dm=dm, steps_per_admit=cell["steps_per_admit"], max_slots=cell["max_slots"],
        setup_s=setup_s, t0=t0, t1=t1, ticks=window_ticks, reqs=drv.reqs, peak=peak,
        counters=sv.counters,
    )
    wreqs = rec.window_reqs()
    late = [r.t_submit - r.due for r in drv.reqs if r.phase == "window"]
    log(f"window: {rec.window_s:.3f} s, {len(window_ticks)} steps, "
        f"{sum(k.tokens for k in window_ticks)} tokens, {len(wreqs)} requests completed, "
        f"prompt tokens admitted {sum(k.prefill_tokens for k in window_ticks)}")
    for kind, ks in (("plain", [k for k in window_ticks if not k.admitted]),
                     ("admitting", [k for k in window_ticks if k.admitted])):
        walls = sorted(k.t_end - k.t_start for k in ks)
        if walls:
            log(f"{kind} steps: {len(walls)}, wall median {walls[len(walls) // 2]:.4f} s, "
                f"max {walls[-1]:.4f} s, sum {sum(walls):.3f} s; "
                f"{sum(k.admitted for k in ks)} admitted")
    log(f"before the window: {sum(k.phase == 'pre' for k in drv.ticks)} steps, "
        f"{sum(r.t_last is not None and r.t_last < t0 for r in drv.reqs)} requests completed")
    log(f"generator lateness: p95 {_p95(late) or 0.0:.6f} s, "
        f"max {max(late, default=0.0):.6f} s over {len(late)} submissions")
    log(f"compiles inside the window: {sum(sv.compiles.values())} {sv.compiles}")
    log(f"peak device bytes: {mem_peak}")

    if tracer is not None:
        t_read = time.perf_counter()
        rec.trace = _read_trace(tracer, keep)
        log(f"trace: reduced in {time.perf_counter() - t_read:.1f} s")
    sched.cache = None  # free the pool before the reference runs
    return Measured(bench, cell, rec, weights, devs, mem_peak, sv.compiles)


def report(m: Measured, workload: str, seed: int, trace: bool, log=print) -> dict:
    """The result line's object of a measured window: its metrics, then
    the check of the served requests against the reference."""
    rec, summary = m.rec, m.rec.trace

    key = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in metrics_for(m.bench, workload, key):
        v = metric_reader(spec["name"])(rec)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}

    # --- correctness: the served requests against the reference ---
    wreqs = rec.window_reqs()
    picked = check.sample(wreqs, np.random.default_rng([seed, 2]), m.cell["check"]["requests"])
    gc.collect()
    t_ref = time.perf_counter()
    read = check.readings(m.weights, rec.dm, picked)
    ok, checks = check.verdict(read, m.cell["check"]["limits"])
    log(f"reference: {read['requests']} requests, {read['tokens']} served tokens "
        f"compared in {time.perf_counter() - t_ref:.1f} s")

    dev = m.devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(m.devs),
              "memory_peak_bytes": m.memory_peak_bytes}
    if summary is not None:
        device["busy_s"] = summary.base.busy_s
        device["window_s"] = summary.base.window_s
    out = {"correct": bool(ok), "attempted": len(wreqs), "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps[:layers.TOP]}
    out["checks"] = checks
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, root: Path = ROOT, require_tpu: bool = True,
        fault=None, log=print) -> dict:
    """Make one run and return the result line's object."""
    m = measure(workload, seed, seconds, trace, t_process=t_process, root=root,
                require_tpu=require_tpu, fault=fault, log=log)
    return report(m, workload, seed, trace, log=log)


def main(argv=None, *, t_process: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        # measure() is called from here, not through run(): with that one
        # Python frame fewer above the warm-up, lowering its programs took
        # about 6 s less on a TPU v5e, a tenth of set-up
        m = measure(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_process=t_process, log=log)
        out = report(m, args.workload, args.seed, bool(args.trace), log=log)
    except Refused as e:
        log(f"bench: refused: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
