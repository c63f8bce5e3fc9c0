"""The metric readers' arithmetic on a hand-made record, and the readers
of the traced window and the scheduler's counters against the numbers of
bench/layers.py."""
from pathlib import Path

import numpy as np
import pytest

from bench import flops, harness, layers
from bench.arch import qwen2
from bench.tests.test_layers import nested
from bench.workload import Req

DM = qwen2.Dims(model_type="qwen2", n_layers=2, d=4, n_q=2, n_kv=1, d_head=2, d_ff=8,
                vocab=16, vocab_rows=16, tied=False, eps=1e-6, theta=1e4, dtype="float32",
                sync_interval=2, participants=2)


def tick(t0, t1, admitted=0, resident=4, tokens=8, prefill=0, work=()):
    return harness.Tick(t_start=t0, t_end=t1, admitted=admitted, resident=resident,
                        tokens=tokens, prefill_tokens=prefill, work=list(work),
                        phase="window")


def req(i, due, t_admit, t_first, t_last, n_new=5, phase="window"):
    return Req(idx=i, tokens=np.zeros(3, np.int32), n_new=n_new, due=due,
               t_admit=t_admit, t_first=t_first, t_last=t_last, phase=phase)


def record():
    ticks = [tick(0.0, 0.1, tokens=10, work=[(3, 1, 3)]),
             tick(0.1, 0.3, admitted=2, resident=6, tokens=20, prefill=1000,
                  work=[(3, 0, 3), (3, 3, 5)]),
             tick(0.3, 0.4, tokens=10), tick(0.4, 0.8, admitted=1, resident=8, tokens=10,
                                             prefill=500, work=[(5, 0, 2)])]
    reqs = [req(i, due=0.01 * i, t_admit=0.02 * i + 0.1, t_first=0.05 * i + 0.3,
                t_last=0.05 * i + 0.7) for i in range(20)]
    reqs.append(req(99, due=-1.0, t_admit=0.0, t_first=0.1, t_last=0.2, phase="pre"))
    return harness.Record(
        dm=DM, steps_per_admit=2, max_slots=8, setup_s=12.5, t0=0.0, t1=0.8,
        ticks=ticks, reqs=reqs, peak={"bf16_flops": 1000.0})


def read(name, rec):
    return harness.metric_reader(name)(rec)


def test_window_rate_is_all_tokens_over_all_time():
    assert read("output_tok_s", record()) == pytest.approx(50 / 0.8)


def test_tpot_p95_is_over_the_requests_the_window_completed():
    rec = record()
    # t_last <= 0.8: requests 0-2 and the one sent before the window
    done = [r for r in rec.reqs if r.t_last <= 0.8]
    assert len(rec.window_reqs()) == len(done) == 4
    tpot = [(r.t_last - r.t_first) / 4 for r in done]
    assert read("tpot_p95_ms", rec) == pytest.approx(np.percentile(tpot, 95) * 1e3)


def test_step_arithmetic():
    rec = record()
    step = (0.1 + 0.1) / 2 / 2  # two plain steps of 0.1 s, 2 sub-steps each
    assert read("decode_step_ms", rec) == pytest.approx(step * 1e3)
    assert read("slots_busy_pct", rec) == pytest.approx(100 * 22 / 32)
    work = (flops.decode_flops(DM, 3, 0, 2) + flops.prefill_flops(DM, 3)
            + flops.decode_flops(DM, 3, 0, 2) + flops.decode_flops(DM, 3, 2, 4)
            + flops.prefill_flops(DM, 5) + flops.decode_flops(DM, 5, 0, 1))
    assert read("mfu", rec) == pytest.approx(100 * work / (0.8 * 1000.0))
    assert read("setup_s", rec) == 12.5
    assert read("idle_pct", rec) is None  # no trace in this record


SCOPED = Path(__file__).parent / "data" / "scoped.xplane.pb"
AT_START = {"prefill_tokens": 1200, "prefill_padded_tokens": 2048, "full_prefills": 5}
AT_END = {"prefill_tokens": 1735, "prefill_padded_tokens": 2880, "full_prefills": 9}


def traced(summary):
    rec = record()
    rec.counters = {k: AT_END[k] - AT_START[k] for k in AT_END}
    rec.trace = summary
    return rec


@pytest.mark.parametrize("events", [lambda: layers.read(str(SCOPED)), nested],
                         ids=["scoped.xplane.pb", "nested"])
def test_trace_and_counter_readers_are_bench_layers_numbers(events):
    s = layers.summarize(events())
    rec = traced(s)
    want = {
        "decode_device_ms": layers.decode_device_ms(s, rec.steps_per_admit),
        "decode_attn_pct": layers.decode_attn_pct(s),
        "prefill_ms_per_ktok": layers.prefill_ms_per_ktok(s, 535),
        "admit_pad_pct": 100 * (832 - 535) / 832,
        "step_host_ms": layers.step_host_ms(s),
        "idle_pct": s.base.idle_pct,
    }
    assert {name: read(name, rec) for name in want} == want
    assert want["step_host_ms"] is not None and want["prefill_ms_per_ktok"] is not None


def test_trace_readers_without_a_trace_read_nothing():
    rec = record()
    for name in ("decode_device_ms", "decode_attn_pct", "prefill_ms_per_ktok",
                 "step_host_ms", "admit_pad_pct"):
        assert read(name, rec) is None
