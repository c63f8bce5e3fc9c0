"""Smoke run of the FedAttn serving path on a TPU.

Serves qwen2-7b at its published widths (d_model 3584, 28 q / 4 kv heads of
128, d_ff 18944, vocab 152064, bfloat16) with only the depth cut to 8 of
its 28 layers — two whole sync periods — on random weights drawn from
``--seed``. The requests go through the entry points a user calls
(``FedAttnEngine`` + ``ContinuousBatchingScheduler``, as
``launch/serve.py --stream`` drives them) over the paged KV pool, once with
the default backend and once with ``backend="pallas"``.

Checks (any failure exits non-zero before the last line is printed):

* every emitted logprob is finite and <= 0, every request got ``n_new``
  tokens;
* one resident ``decode_step`` executable per pool;
* the jaxpr audit of the pool's entry points is clean (donation as
  declared for the TPU, no f64, no callbacks, nothing baked in);
* the pallas pool's compiled decode step contains ``tpu_custom_call`` —
  the Pallas kernel was compiled, not interpreted;
* the first token's logits from the engine's bucketed prefill agree with
  the uncached ``TransformerLM.apply(..., head_mode="last")`` on the same
  prompt and FedAttn context, and the two backends agree with each other
  (tolerances at ``LOGIT_TOL``).

``--mesh 4`` instead serves the same trace through the capacity-sharded
pool on a 4-device serving mesh and compares it with the single-device
pool; nothing else runs.

Usage (from the checkout root, on a machine with the chip):
  python chip_smoke.py            # one chip
  python chip_smoke.py --mesh 4   # four chips

The last line of standard output is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any throughput printed is a smoke reading of a short trace, not a
metric.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "qwen2-7b"
N_LAYERS = 8  # two sync periods of 4
PARTICIPANTS = 4
SYNC_INTERVAL = 4
PROMPT_LENS = (256, 512, 640, 1024, 1200, 1536, 1800, 2048)
N_NEW = 32
MAX_SLOTS = 8
STEPS_PER_ADMIT = 4
LOGIT_PROMPT = 2  # the 640-token prompt: pads to its 1024 bucket

# Logit tolerances, as max|a - b| / max|b| over the vocab. Weights and
# activations are bfloat16 (8 significant bits, relative rounding 2^-9),
# and the compared paths round differently: the engine's prefill
# bulk-writes K/V into a bf16 cache and attends over the padded bucket,
# the reference attends within the prompt; the backends differ in
# softmax association (online vs blocked). Eight layers of residual
# updates compound a few bf16 roundings per element, so paths agree to a
# few percent of the logit range, not to f32 rounding. A real fault
# (wrong mask, wrong head, wrong page) moves logits by O(1) of the range.
LOGIT_TOL = 0.05
# --mesh 4: the sharded flash-decode combine reassociates the softmax
# sums. A greedy token may flip where two candidates are near-tied, after
# which the two streams diverge; up to and including each request's first
# differing token, the emitted tokens' logprobs must agree within this:
# two bf16 ulps of a logit below 16 in magnitude (these random weights
# give |logit| < 10), since logits are bf16 and the emitted token's logit
# and the normalizer may each round one ulp apart.
MESH_LOGPROB_TOL = 0.125


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def import_repo():
    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script ({src}): {e}")
    return enable_compile_cache


def make_requests(vocab: int, seed: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(tokens=jnp.asarray(rng.integers(3, vocab, size=(L,)), jnp.int32),
                n_new=N_NEW)
        for L in PROMPT_LENS
    ]


def fed_config():
    from repro.types import FedAttnConfig

    return FedAttnConfig(
        n_participants=PARTICIPANTS, sync_interval=SYNC_INTERVAL
    )


def serve(engine, reqs, label: str):
    """Serve the trace twice through one pool (the first pass compiles);
    check the outputs and return (results, scheduler)."""
    import numpy as np
    from repro.serving.scheduler import ContinuousBatchingScheduler

    cap = ContinuousBatchingScheduler.capacity_for(engine, reqs)
    sched = ContinuousBatchingScheduler(
        engine, max_slots=MAX_SLOTS, capacity=cap,
        steps_per_admit=STEPS_PER_ADMIT,
    )
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        results = sched.run(reqs)
        walls.append(time.perf_counter() - t0)
    n_tok = sum(r.tokens.shape[1] for r in results)
    print(f"[{label}] pool {MAX_SLOTS} slots x {cap} tokens "
          f"({sched.num_pages} pages of {sched.page_size}); "
          f"pass 1 (compiles) {walls[0]:.2f}s, pass 2 {walls[1]:.2f}s, "
          f"{n_tok} tokens per pass (smoke reading {n_tok / walls[1]:.1f} "
          f"tok/s, not a metric)")
    print(f"[{label}] executables: {sched.compile_counts}")
    for i, r in enumerate(results):
        check(r.tokens.shape == (1, N_NEW),
              f"[{label}] request {i}: tokens {r.tokens.shape}")
        lp = np.asarray(r.logprobs)
        check(bool(np.all(np.isfinite(lp))),
              f"[{label}] request {i}: non-finite logprobs")
        check(bool(np.all(lp <= 0.0)),
              f"[{label}] request {i}: logprob > 0 ({lp.max()})")
    check(sched.compile_counts["decode_step"] == 1,
          f"[{label}] decode_step executables "
          f"{sched.compile_counts['decode_step']} != 1")
    return results, sched


def audit_pool(sched, label: str, *, expect_kernel: bool):
    """Jaxpr audit of the pool's entry points; with ``expect_kernel`` the
    compiled decode step must hold the Pallas kernel as a TPU custom
    call."""
    from repro.analysis import jaxpr_audit

    entries = jaxpr_audit.trace_scheduler_entries(sched)
    issues = jaxpr_audit.audit_entries(entries)
    check(issues == [], f"[{label}] audit: " + "; ".join(map(str, issues)))
    if expect_kernel:
        step = next(e for e in entries if e.name == "scheduler.decode_step")
        with sched._spmd_scope():
            text = step.traced.lower().compile().as_text()
        check("tpu_custom_call" in text,
              f"[{label}] compiled decode step holds no tpu_custom_call")
        print(f"[{label}] compiled decode step holds the Pallas kernel "
              f"({text.count('tpu_custom_call')} tpu_custom_call sites)")
    print(f"[{label}] jaxpr audit clean ({len(entries)} entry points)")


def prefill_logits(engine, req):
    """First-token logits (V,) from the engine's bucketed prefill."""
    toks = req.tokens[None]
    L = toks.shape[1]
    Lp = engine._bucket_len(L)
    cap = Lp + engine._bucket_new(req.n_new)
    plan = engine._plan if engine.layers_mode == "scan" else None
    cache = engine.model.init_cache(1, cap, plan=plan)
    ctx = engine.build_context(L)
    last, _ = engine._prefill_compiled(toks, ctx, cache, None, L, Lp, cap)
    return last[0]


def reference_logits(engine, req):
    """The same logits from the uncached forward (no KV cache, no
    bucketing): ``TransformerLM.apply(..., head_mode="last")``."""
    import jax

    toks = req.tokens[None]
    ctx = engine.build_context(toks.shape[1])
    mode = "scan" if "stacked" in engine.params else "loop"
    fn = jax.jit(lambda p, t: engine.model.apply(
        p, t, ctx, mode=mode, head_mode="last"))
    return fn(engine.params, toks)[0, -1]


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
          "non-finite logits")
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def one_chip(config, seed: int, *, expect_kernel: bool = True):
    from repro.serving import FedAttnEngine

    reqs = make_requests(config.vocab_size, seed)
    logits = {}
    params = None
    for backend in (None, "pallas"):
        label = backend or "default"
        engine = FedAttnEngine(config, params, fedattn=fed_config(),
                               backend=backend)
        if params is None:
            t0 = time.perf_counter()
            engine.init_random_params(seed)
            params = engine.params
            print(f"init: {time.perf_counter() - t0:.2f}s, "
                  f"{'scan' if 'stacked' in params else 'loop'}-form weights, "
                  f"layers lowered as {engine.layers_mode}")
        _, sched = serve(engine, reqs, label)
        audit_pool(sched, label, expect_kernel=expect_kernel and backend == "pallas")
        del sched
        logits[label] = prefill_logits(engine, reqs[LOGIT_PROMPT])
        if backend is None:
            ref = reference_logits(engine, reqs[LOGIT_PROMPT])
    L = PROMPT_LENS[LOGIT_PROMPT]
    for label, lg in logits.items():
        err = rel_err(lg, ref)
        print(f"logits [{label} prefill vs uncached apply], L={L}: "
              f"max rel err {err:.5f} (tol {LOGIT_TOL})")
        check(err <= LOGIT_TOL, f"{label} prefill logits vs reference {err}")
    err = rel_err(logits["pallas"], logits["default"])
    print(f"logits [pallas vs default prefill]: max rel err {err:.5f} "
          f"(tol {LOGIT_TOL})")
    check(err <= LOGIT_TOL, f"pallas vs default prefill logits {err}")


def compare_streams(a, b):
    """Token agreement and the near-tie rule of ``MESH_LOGPROB_TOL``."""
    import numpy as np

    same = total = 0
    worst = 0.0
    for i, (ra, rb) in enumerate(zip(a, b)):
        ta, tb = np.asarray(ra.tokens)[0], np.asarray(rb.tokens)[0]
        la, lb = np.asarray(ra.logprobs)[0], np.asarray(rb.logprobs)[0]
        diff = np.nonzero(ta != tb)[0]
        d = int(diff[0]) if diff.size else len(ta)
        same += d
        total += len(ta)
        upto = min(d + 1, len(ta))
        err = float(np.max(np.abs(la[:upto] - lb[:upto])))
        worst = max(worst, err)
        check(err <= MESH_LOGPROB_TOL,
              f"request {i}: logprobs differ by {err} before/at its first "
              f"token divergence (step {d})")
    return same, total, worst


def mesh(config, seed: int, n: int):
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import FedAttnEngine

    reqs = make_requests(config.vocab_size, seed)
    engine = FedAttnEngine(config, fedattn=fed_config())
    engine.init_random_params(seed)
    single, sched = serve(engine, reqs, "single-device")
    del engine, sched  # frees device 0 for the mesh run's replica

    engine = FedAttnEngine(config, fedattn=fed_config(),
                           mesh=make_serving_mesh(n))
    engine.init_random_params(seed)
    sharded, sched = serve(engine, reqs, f"mesh {n}")
    audit_pool(sched, f"mesh {n}", expect_kernel=False)
    same, total, worst = compare_streams(single, sharded)
    print(f"mesh {n} vs single-device: {same}/{total} tokens identical "
          f"before each request's first divergence; max logprob diff up to "
          f"it {worst:.5f} (tol {MESH_LOGPROB_TOL})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="serve through the capacity-sharded pool on an "
                         "N-device mesh and compare with one device "
                         "(nothing else runs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache = import_repo()
    cache_dir = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no accelerator: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {len(devices)} {dev.platform} device(s)")
    want = args.mesh or 1
    check(len(devices) >= want, f"needs {want} TPU devices, found {len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")

    from repro.launch.serve import build_config

    config = build_config(ARCH, N_LAYERS)
    print(f"model: {config.name} d_model={config.d_model} heads={config.n_heads}"
          f"/{config.n_kv_heads}x{config.head_dim} d_ff={config.d_ff} "
          f"vocab={config.vocab_size} {config.dtype}, {config.n_layers} layers, "
          f"{config.param_count() / 1e9:.2f}B params; N={PARTICIPANTS} "
          f"H={SYNC_INTERVAL}, prompts {PROMPT_LENS}, n_new={N_NEW}")
    t0 = time.perf_counter()
    if args.mesh:
        mesh(config, args.seed, args.mesh)
    else:
        one_chip(config, args.seed)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"wall {time.perf_counter() - t0:.1f}s; peak device bytes "
          f"{peak if peak is not None else 'not reported'}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
