"""What the serving path leaves in a profiler trace: the host spans of
``step()`` (serving/spans.py), the names of the jitted entry points, the
model's named scopes (models/scopes.py), and the padded-prefill counter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from repro.models import scopes
from repro.serving import FedAttnEngine, Request
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.serving.spans import SPANS


def _engine(cfg=None):
    from repro.models import build_model

    cfg = cfg or tiny_config()
    return FedAttnEngine(cfg, build_model(cfg).init(jax.random.key(0)))


@pytest.fixture(scope="module")
def eng():
    return _engine()


def _req(i, L, n_new):
    toks = jax.random.randint(jax.random.key(10 + i), (L,), 0, tiny_config().vocab_size)
    return Request(tokens=toks, n_new=n_new)


def _host_spans(path):
    """[(name, start, end, request ids)] of the ``serve.`` spans, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    stats = dict(e.stats)
                    rids = [int(x) for x in re.findall(r"\d+", str(stats.get("rids", "")))]
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, rids))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def test_step_spans_nest_in_order_with_request_ids(eng, tmp_path):
    """A traced step() holds one ``serve.step`` span whose children follow
    one another without overlap, in phase order: the queue scan, then per
    admission group pack/dispatch/sync/commit, then the decode call's
    upload/dispatch/sync/commit. Admission spans carry the admitted ids,
    the decode commit the retired ones."""
    import glob

    sched = ContinuousBatchingScheduler(eng, max_slots=4, capacity=48, steps_per_admit=2)
    first = sched.submit(_req(0, 9, 5))
    sched.step()  # compiles outside the trace; `first` holds 3 of its 5 tokens
    with jax.profiler.trace(str(tmp_path)):
        ids = [sched.submit(_req(i, L, 5)) for i, L in ((1, 12), (2, 14))]
        sched.step()  # admits ids (one 16-bucket group), retires `first`
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = _host_spans(path)
    assert {name for name, *_ in spans} <= set(SPANS)
    (step,) = [sp for sp in spans if sp[0] == "serve.step"]
    children = [sp for sp in spans if sp[0] != "serve.step"]
    assert all(step[1] <= s and e <= step[2] for _, s, e, _ in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))  # no overlap
    assert [name for name, *_ in children] == [
        "serve.admit.plan", "serve.admit.pack", "serve.admit.dispatch",
        "serve.admit.sync", "serve.admit.commit", "serve.decode.upload",
        "serve.decode.dispatch", "serve.decode.sync", "serve.decode.commit",
    ]
    for name, _, _, rids in children:
        if name.startswith("serve.admit."):
            assert rids == ids, name
    assert children[-1][3] == [first]


def test_speculative_step_spans(tmp_path):
    """With ``spec_k > 0`` the host drafting has a span of its own
    between admission and the verify call."""
    import glob

    sched = ContinuousBatchingScheduler(_engine(), max_slots=2, capacity=40, spec_k=2)
    sched.submit(_req(0, 10, 6))
    sched.step()
    with jax.profiler.trace(str(tmp_path)):
        sched.step()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    names = [name for name, *_ in _host_spans(path)]
    assert names == ["serve.step", "serve.admit.plan", "serve.draft", "serve.decode.upload",
                     "serve.decode.dispatch", "serve.decode.sync", "serve.decode.commit"]


class _Spy:
    """Stands in for a jitted entry point and keeps its last arguments."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        self.args = args
        return self.fn(*args)

    def lowered(self):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype) if hasattr(a, "shape") else a,
            self.args)
        return self.fn.lower(*shapes)


def _module_name(spy) -> str:
    return re.match(r"module @(\S+)", spy.lowered().as_text()).group(1)


def test_entry_points_name_their_modules():
    """Each jitted entry point lowers to a module named after it, which is
    how the profiler's XLA Modules line names its executions."""
    eng = _engine()
    sched = ContinuousBatchingScheduler(eng, max_slots=4, capacity=48, steps_per_admit=2,
                                        prefix_cache=True)
    # three prompts of one length (the partition's segments depend on it)
    # that share their first page: the later two hit the prefix cache
    head = np.asarray(_req(0, 20, 1).tokens)
    prompts = [np.concatenate([head, head[a: a + 5]]) for a in (0, 7, 3)]
    sched.submit(Request(tokens=prompts[0], n_new=3))
    sched.step()  # builds prefill, admit_finish, slot_write, decode_step
    sched._step_fns[2] = step = _Spy(sched._step_fns[2])
    sched._admit_fn = finish = _Spy(sched._admit_fn)
    sched._write_fn = write = _Spy(sched._write_fn)
    sched.submit(Request(tokens=prompts[1], n_new=2))
    sched.step()  # builds suffix_prefill
    for k, fn in list(eng._prefill_fns.items()):
        eng._prefill_fns[k] = _Spy(fn)
    sched.submit(Request(tokens=prompts[2], n_new=2))
    sched.submit(_req(1, 30, 2))  # the first prompt's bucket
    while not sched.done():
        sched.step()
    assert sched.stats["suffix_prefills"] == 2
    names = {_module_name(s) for s in (step, finish, write)}
    names |= {_module_name(s) for s in eng._prefill_fns.values()
              if isinstance(s, _Spy) and s.args is not None}
    assert names == {"jit_decode_step", "jit_admit_finish", "jit_slot_write",
                     "jit_prefill", "jit_suffix_prefill"}

    eng.generate(_req(2, 8, 4).tokens[None], 4)
    (key,) = eng._decode_fns
    eng._decode_fns[key] = driver = _Spy(eng._decode_fns[key])
    eng.generate(_req(3, 8, 4).tokens[None], 4)
    assert _module_name(driver) == "jit_decode_driver"

    spec = ContinuousBatchingScheduler(eng, max_slots=2, capacity=40, spec_k=2)
    spec.submit(_req(4, 10, 12))
    spec.step()
    spec._verify = verify = _Spy(spec._verify)
    spec.step()
    assert _module_name(verify) == "jit_verify_step"


def _scopes_in(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {s for s in scopes.SCOPES if re.search(rf'[/"]{s}/', text)}


def test_decode_and_prefill_steps_carry_the_model_scopes():
    """Every part of a paged decode step and of an admission prefill is
    traced under one of the model's scopes, each in its own place. Eight
    layers make two periods of the sync schedule, so the layers lower as a
    scan (as in the benchmark's configurations)."""
    eng = _engine(tiny_config(n_layers=8))
    assert eng._plan.n_periods == 2
    sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=40, steps_per_admit=1)
    sched.submit(_req(0, 10, 2))
    sched.step()
    (k,) = sched._step_fns
    sched._step_fns[k] = step = _Spy(sched._step_fns[k])
    for key, fn in list(eng._prefill_fns.items()):
        eng._prefill_fns[key] = _Spy(fn)
    sched.submit(_req(1, 12, 2))
    sched.step()
    (prefill,) = [s for s in eng._prefill_fns.values()
                  if isinstance(s, _Spy) and s.args is not None]
    assert _scopes_in(step.lowered()) == set(scopes.SCOPES) - {scopes.PREFILL_ATTENTION}
    assert _scopes_in(prefill.lowered()) == set(scopes.SCOPES) - {scopes.DECODE_ATTENTION}


def test_prefill_padded_tokens_counts_rows_times_bucket(eng):
    """Three prompts in the 16-token bucket admit as one group padded to 4
    rows: 4 x 16 prefilled positions, of which 9 + 12 + 14 are real; a lone
    5-token prompt then adds one row of its 8-token bucket."""
    sched = ContinuousBatchingScheduler(eng, max_slots=4, capacity=48)
    for i, L in enumerate((9, 12, 14)):
        sched.submit(_req(i, L, 2))
    sched.step()
    assert sched.stats["prefill_tokens"] == 35
    assert sched.stats["prefill_padded_tokens"] == 4 * 16
    sched.submit(_req(3, 5, 2))
    sched.step()
    assert sched.stats["prefill_tokens"] == 40
    assert sched.stats["prefill_padded_tokens"] == 4 * 16 + 8
