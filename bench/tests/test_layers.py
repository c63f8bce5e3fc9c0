"""The trace reduction to the program's layers (bench/layers.py): the
decode of op metadata, entry points and scopes, innermost idle
attribution, the numbers these give, and that what bench/trace.py
computes is left as it was."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import layers, trace
from bench.tests.test_trace import MS, synthetic

DATA = Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb"
LAMBDA_ID = 12463548226743970204  # the program id of small.xplane.pb's one module


def test_decoded_intervals_are_profile_datas():
    """Every op and host span decoded here has the interval that
    ProfileData gives the same event, so both reductions share one clock."""
    ev = layers.read(str(SMALL))
    assert ev.base == trace.read(str(SMALL))
    prof = ProfileData.from_file(str(SMALL))
    (dev,) = [p for p in prof.planes if p.name == "/device:TPU:0"]
    lines = {ln.name: ln for ln in dev.lines}
    want = [(e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Ops"].events]
    assert [(s, e) for *_, s, e in ev.ops["/device:TPU:0"]] == want
    want = [(e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Modules"].events]
    assert [(s, e) for _, s, e in ev.modules["/device:TPU:0"]] == want
    assert sorted((n, s, e) for n, s, e, _ in ev.spans) == sorted(ev.base.spans)


def test_op_metadata_of_the_recorded_trace():
    """The fusion of small.xplane.pb carries the HLO op_name of its root and
    the program id that its module's name ends in."""
    with open(SMALL, "rb") as f:
        space = layers._schema().FromString(f.read())
    (dev,) = [p for p in space.planes if p.name == "/device:TPU:0"]
    names = {k: v.name for k, v in dev.stat_metadata.items()}
    meta = {md.name.split(" = ")[0]: layers._stats(md.stats, names)
            for md in dev.event_metadata.values()}
    assert meta["%fusion"]["tf_op"] == "jit(<lambda>)/dot_general:"
    assert meta["%fusion"]["program_id"] == LAMBDA_ID
    assert f"jit__lambda({LAMBDA_ID})" in meta
    ops = layers.read(str(SMALL)).ops["/device:TPU:0"]
    # the fusion traced from the program; the copies XLA put round it
    assert {(entry, scope) for entry, scope, *_ in ops} == {("_lambda", "unscoped"),
                                                           ("_lambda", "compiler")}


def test_what_trace_computes_is_unchanged():
    """Window, busy time, waits and idle share are bench/trace.py's, with
    the values it gave before the program's spans and scopes existed."""
    s = layers.summarize(layers.read(str(SMALL)))
    assert s.base == trace.summarize(trace.read(str(SMALL)))
    assert s.base.window_s == pytest.approx(0.045369075, rel=1e-12)
    assert s.base.busy_s == pytest.approx(0.000316281, rel=1e-12)
    assert s.idle_pct == pytest.approx(98.97499715155475, rel=1e-12)
    syn = layers.summarize(layers.Events(base=synthetic()))
    assert syn.base == trace.summarize(synthetic())
    busy = (28 - 2) + (90 - 62) + (100 - 95)
    assert syn.idle_pct == pytest.approx(100 * (1 - busy / 80))


def test_spans_that_follow_one_another_split_idle_as_trace_does():
    """Without nesting, innermost attribution is trace.py's split."""
    s = layers.summarize(layers.Events(base=(base := synthetic()),
                                       spans=[(n, a, b, {}) for n, a, b in base.spans]))
    assert dict(s.idle_gaps) == pytest.approx(dict(s.base.idle_gaps))


def nested():
    """bench.decode_step > serve.step > the decode phases, then bench's
    bookkeeping; one module run whose ops cover part of the sync."""
    spans = [
        ("bench.window", 0, 60 * MS),
        ("bench.decode_step", 0, 40 * MS),
        ("serve.step", 1 * MS, 40 * MS),
        ("serve.decode.upload", 1 * MS, 10 * MS),
        ("serve.decode.dispatch", 10 * MS, 12 * MS),
        ("serve.decode.sync", 12 * MS, 35 * MS),
        ("serve.decode.commit", 35 * MS, 39 * MS),
        ("bench.bookkeeping", 40 * MS, 50 * MS),
    ]
    base = trace.Events(spans=[sp for sp in spans if sp[0].startswith("bench.")])
    dev = "/device:TPU:0"
    ops = [
        ("decode_step", "unscoped", "while.3", 11 * MS, 30 * MS),
        ("decode_step", "decode_attention", "fusion.1", 11 * MS, 25 * MS),
        ("decode_step", "mlp", "fusion.2", 25 * MS, 29 * MS),
        ("decode_step", "unscoped", "copy.4", 29 * MS, 30 * MS),
        ("slot_write", "unscoped", "scatter.5", 52 * MS, 55 * MS),
    ]
    base.ops[dev] = [(op, s, e) for _, _, op, s, e in ops]
    return layers.Events(
        base=base, ops={dev: ops},
        modules={dev: [("decode_step", 11 * MS, 30 * MS), ("slot_write", 52 * MS, 55 * MS)]},
        spans=[(n, a, b, {}) for n, a, b in spans])


def test_idle_goes_to_the_innermost_span():
    s = layers.summarize(nested())
    gaps = dict(s.idle_gaps)
    assert gaps == pytest.approx({
        "bench.decode_step": 1e-3,  # before serve.step opens
        "serve.decode.upload": 9e-3,
        "serve.decode.dispatch": 1e-3,  # 10-11 ms, before the device starts
        "serve.decode.sync": 5e-3,  # 30-35 ms, after it stops
        "serve.decode.commit": 4e-3,
        "serve.step": 1e-3,  # 39-40 ms
        "bench.bookkeeping": 10e-3,
        "bench.other": 7e-3,  # 50-52 and 55-60 ms: no span
    })
    assert sum(gaps.values()) == pytest.approx(s.base.window_s - s.base.busy_s)
    # bench/trace.py puts the whole of it under the harness's span
    assert dict(s.base.idle_gaps)["bench.decode_step"] == pytest.approx(21e-3)


def test_device_time_by_entry_point_and_scope():
    s = layers.summarize(nested())
    assert s.entry_s == pytest.approx({"decode_step": 19e-3, "slot_write": 3e-3})
    assert s.entry_runs == {"decode_step": 1, "slot_write": 1}
    assert s.scope_s == pytest.approx({("decode_step", "decode_attention"): 14e-3,
                                       ("decode_step", "mlp"): 4e-3,
                                       ("decode_step", "unscoped"): 1e-3,
                                       ("slot_write", "unscoped"): 3e-3})
    names = [name for name, _ in s.device_ops]
    assert names[0] == "decode_step/decode_attention/fusion.1"
    assert not any("while" in name for name in names)
    assert layers.decode_attn_pct(s) == pytest.approx(100 * 14 / 19)
    assert layers.scope_pct(s, "decode_step", "unscoped") == pytest.approx(100 / 19)
    assert layers.decode_device_ms(s, steps_per_admit=4) == pytest.approx(19 / 4)
    assert layers.prefill_ms_per_ktok(s, prefill_tokens=500) == pytest.approx(3 / 0.5)
    # serve.step 39 ms less its 23 ms sync
    assert layers.step_host_ms(s) == pytest.approx(39 - 23)


def test_scope_of_a_path():
    """The innermost known scope names an op; an op traced outside every
    scope is unscoped, one that XLA inserted (no path) the compiler's."""
    scopes = ("qkv", "decode_attention", "mlp", "layer_scan")
    assert layers.scope_of("jit(decode_step)/while/body/closed_call/layer_scan/while/body/"
                           "closed_call/decode_attention/dot_general", scopes) == "decode_attention"
    assert layers.scope_of("jit(decode_step)/while/body/closed_call/layer_scan/while/body/"
                           "dynamic_update_slice", scopes) == "layer_scan"
    assert layers.scope_of("jit(decode_step)/while/body/dynamic_slice", scopes) == "unscoped"
    assert layers.scope_of("", scopes) == "compiler"
    assert not layers.is_leaf("while.35") and layers.is_leaf("fusion.12")
    assert not layers.is_leaf("conditional") and layers.is_leaf("call-start.1")


def test_numbers_without_their_data_are_none():
    s = layers.summarize(layers.Events(base=synthetic()))
    assert layers.decode_device_ms(s, 4) is None
    assert layers.decode_attn_pct(s) is None
    assert layers.prefill_ms_per_ktok(s, 0) is None
    assert layers.step_host_ms(s) is None
    assert layers.admit_pad_pct({"prefill_tokens": 0, "prefill_padded_tokens": 0}) is None
    assert layers.admit_pad_pct({}) is None
    assert layers.admit_pad_pct({"prefill_tokens": 35, "prefill_padded_tokens": 64}) \
        == pytest.approx(100 * 29 / 64)
    assert layers.summarize(layers.Events(base=trace.Events())) is None


def test_profile_tool_at_a_tiny_size(tiny_root):
    """The tool runs a cell's window on the CPU; with no TPU plane in the
    trace there are no layers, but the counters moved."""
    from bench import profile_cell

    out = profile_cell.profile("tiny.closed", 2**31 + 11, 1.0, root=tiny_root,
                               require_tpu=False, log=lambda m: None)
    assert out["layers"] is None
    c = out["counters"]
    assert c["prefill_padded_tokens"] >= c["prefill_tokens"] > 0
    assert out["window"]["steps"] > 0 and out["window"]["compiles"]


SCOPED = DATA / "scoped.xplane.pb"


def test_recorded_scoped_trace():
    """A trace recorded on a TPU v5e by ``record_scoped_trace.py``: four
    runs of ``scoped_step``, a fori_loop over a ``decode_attention`` scope
    with eight times the FLOPs of its ``mlp`` scope, under nested spans."""
    s = layers.summarize(layers.read(str(SCOPED)))
    prof = ProfileData.from_file(str(SCOPED))
    (dev,) = [p for p in prof.planes if p.name == "/device:TPU:0"]
    lines = {ln.name: ln for ln in dev.lines}
    host = [e for p in prof.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    (win,) = [e for e in host if e.name == "bench.window"]
    lo, hi = win.start_ns, win.start_ns + win.duration_ns
    runs = [e for e in lines["XLA Modules"].events
            if e.name.startswith("jit_scoped_step(") and lo <= e.start_ns < hi]
    assert len(runs) == 4 == s.entry_runs["scoped_step"]
    assert s.entry_s["scoped_step"] == pytest.approx(sum(e.duration_ns for e in runs) * 1e-9)
    # the loop's while op spans its body's ops and is left out of op times
    whiles = [e for e in lines["XLA Ops"].events if e.name.startswith("%while")]
    assert len(whiles) == 4
    assert not any("while" in name for name, _ in s.device_ops)
    leaf = sum(t for (e, _), t in s.scope_s.items() if e == "scoped_step")
    assert leaf <= s.base.busy_s < leaf + sum(e.duration_ns for e in whiles) * 1e-9
    # scope shares follow the FLOPs (8/9 and 1/9); XLA's own copies are small
    assert layers.scope_pct(s, "scoped_step", "decode_attention") == pytest.approx(88.6, abs=3)
    assert layers.scope_pct(s, "scoped_step", "mlp") == pytest.approx(10.4, abs=3)
    assert layers.scope_pct(s, "scoped_step", "compiler") < 2
    assert ("scoped_step", "unscoped") not in s.scope_s
    # the host's sleep in serve.decode.upload is idle time of that span
    # (less the device time the trace places inside it: the device's clock
    # runs about a millisecond ahead of the host's there), which
    # bench/trace.py puts under the harness's bench.decode_step
    busy = trace.union([(e.start_ns, e.start_ns + e.duration_ns)
                        for e in lines["XLA Ops"].events], lo, hi)
    uploads = [(e.start_ns, e.start_ns + e.duration_ns) for e in host
               if e.name == "serve.decode.upload"]
    idle = sum(b - a - sum(trace._overlap(a, b, c, d) for c, d in busy) for a, b in uploads)
    assert dict(s.idle_gaps)["serve.decode.upload"] == pytest.approx(idle * 1e-9)
    assert dict(s.base.idle_gaps)["bench.decode_step"] > idle * 1e-9
    assert s.base == trace.summarize(trace.read(str(SCOPED)))
