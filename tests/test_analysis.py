"""The invariant analyzer analyzed: every rule/check must catch its seeded
regression, and the real tree must be clean.

Three layers:

* lint rules (FED001-FED005) against seeded source fixtures — each rule
  fires on its target pattern, stays quiet on the blessed idiom, and the
  ``# fedlint: disable=FEDxxx`` escape hatch works;
* jaxpr audits against seeded traced fixtures — a private f64 op, a host
  callback, a dropped donation and a baked-in buffer are each caught;
* the repo itself — ``src/`` lints clean, and every registered
  architecture's serving entry points trace clean with ZERO compilations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from repro.analysis import jaxpr_audit, lint, trace_guard
from repro.analysis.trace_guard import BudgetExceeded, TraceGuard
from repro.configs import ASSIGNED_ARCHS
from repro.models import build_model
from repro.serving.engine import FedAttnEngine, _donation_for_backend
from repro.types import FedAttnConfig, LayerSpec

pytestmark = pytest.mark.analysis


def _rules_of(violations):
    return {v.rule for v in violations}


# ---------------------------------------------------------------------------
# lint rules: each catches its seeded regression
# ---------------------------------------------------------------------------


class TestLintRules:
    def test_rule_table_complete(self):
        table = lint.rules()
        assert set(table) == {
            "FED001", "FED002", "FED003", "FED004", "FED005", "FED006",
            "FED007",
        }
        assert all(table.values())  # every rule has a one-line summary

    def test_fed001_private_mask_copy(self):
        # the seeded regression from ISSUE.md: a module quietly re-deriving
        # the masking NEG_INF instead of importing kernels/core's
        src = "import jax.numpy as jnp\nNEG_INF = -0.7 * 3.4e38\n"
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED001" in _rules_of(vs)

    def test_fed001_visibility_redefinition(self):
        src = "def visibility(q_pos, kv_pos):\n    return q_pos >= kv_pos\n"
        vs = lint.lint_source(src, "repro/serving/bad.py")
        assert "FED001" in _rules_of(vs)

    def test_fed001_neg_inf_literal_in_where(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(m, s):\n"
            "    return jnp.where(m, s, -2.38e38)\n"
        )
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED001" in _rules_of(vs)

    def test_fed001_core_and_aliases_allowed(self):
        # core.py itself may define the names; importing the alias is the
        # blessed idiom everywhere else
        core_src = "NEG_INF = -0.7 * 3.4e38\ndef visibility(): pass\n"
        assert lint.lint_source(core_src, "repro/kernels/core.py") == []
        alias = "from repro.kernels.core import NEG_INF\nMASK_VALUE = NEG_INF\n"
        assert lint.lint_source(alias, "repro/models/ok.py") == []

    def test_fed002_bare_segment_sentinel(self):
        # seeded regression: a bare -1 pad where PAD_SEGMENT is required
        src = (
            "import jax.numpy as jnp\n"
            "def f(seg):\n"
            "    return jnp.pad(seg, (0, 3), constant_values=-1)\n"
        )
        vs = lint.lint_source(src, "repro/serving/bad.py")
        assert "FED002" in _rules_of(vs)

    def test_fed002_seg_compare(self):
        src = "def f(kv_seg):\n    return kv_seg == -2\n"
        vs = lint.lint_source(src, "repro/kernels/bad.py")
        assert "FED002" in _rules_of(vs)

    def test_fed002_named_constant_clean(self):
        src = (
            "import jax.numpy as jnp\n"
            "from repro.kernels.core import PAD_SEGMENT\n"
            "def f(seg):\n"
            "    return jnp.pad(seg, (0, 3), constant_values=PAD_SEGMENT)\n"
        )
        assert lint.lint_source(src, "repro/serving/ok.py") == []

    def test_fed002_index_fill_value_not_flagged(self):
        # nonzero(..., fill_value=-1) fills *indices*, not segments — the
        # rule deliberately does not cover it (core/partition.py idiom)
        src = (
            "import jax.numpy as jnp\n"
            "def f(m):\n"
            "    return jnp.nonzero(m, size=4, fill_value=-1)\n"
        )
        assert lint.lint_source(src, "repro/core/ok.py") == []

    def test_fed003_import_time_array(self):
        src = "import jax.numpy as jnp\nTABLE = jnp.arange(128)\n"
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED003" in _rules_of(vs)

    def test_fed003_static_inspection_allowed(self):
        src = (
            "import jax.numpy as jnp\n"
            "PAD_POS = jnp.iinfo(jnp.int32).max\n"
            "EPS = jnp.finfo(jnp.float32).tiny\n"
        )
        assert lint.lint_source(src, "repro/models/ok.py") == []

    def test_fed004_np_random_in_hot_module(self):
        src = "import numpy as np\ndef f():\n    return np.random.rand(3)\n"
        vs = lint.lint_source(src, "repro/kernels/bad.py")
        assert "FED004" in _rules_of(vs)
        # cold modules (launch/, tools) may use host randomness
        assert lint.lint_source(src, "repro/launch/ok.py") == []

    def test_fed004_item_in_hot_module(self):
        src = "def f(x):\n    return x.sum().item()\n"
        vs = lint.lint_source(src, "repro/serving/bad.py")
        assert "FED004" in _rules_of(vs)

    def test_fed004_float_on_tracer(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    return float(jnp.sum(x))\n"
        )
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED004" in _rules_of(vs)
        # static inspection stays legal (the NEG_INF definition idiom)
        ok = (
            "import jax.numpy as jnp\n"
            "def f():\n"
            "    return -0.7 * float(jnp.finfo(jnp.float32).max)\n"
        )
        assert lint.lint_source(ok, "repro/models/ok.py") == []

    def test_fed005_python_branch_on_tracer(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    if jnp.any(x > 0):\n"
            "        return x\n"
            "    return -x\n"
        )
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED005" in _rules_of(vs)

    def test_fed005_static_branch_allowed(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    if jnp.ndim(x) == 2:\n"
            "        return x\n"
            "    return x[None]\n"
        )
        assert lint.lint_source(src, "repro/models/ok.py") == []

    def test_escape_hatch_line_and_file(self):
        src = "def visibility(q, k):  # fedlint: disable=FED001\n    pass\n"
        assert lint.lint_source(src, "repro/serving/ok.py") == []
        # disabling the wrong rule does not silence the finding
        src = "def visibility(q, k):  # fedlint: disable=FED002\n    pass\n"
        assert "FED001" in _rules_of(lint.lint_source(src, "repro/serving/bad.py"))
        filewide = (
            "# fedlint: disable\n"
            "import jax.numpy as jnp\n"
            "TABLE = jnp.arange(128)\n"
            "NEG_INF = -0.7 * 3.4e38\n"
        )
        assert lint.lint_source(filewide, "repro/models/ok.py") == []

    def test_fed006_raw_page_arithmetic(self):
        # seeded regression: a consumer re-deriving page coordinates from
        # linear KV positions by hand instead of paging.page_split
        src = (
            "def f(pos, page_size):\n"
            "    return pos // page_size, pos % page_size\n"
        )
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED006" in _rules_of(vs)
        # ...and modding a slot index by the pool's page count
        src = "def f(i, num_pages):\n    return i % num_pages\n"
        assert "FED006" in _rules_of(lint.lint_source(src, "repro/serving/bad.py"))

    def test_fed006_paging_module_and_blessed_idioms_clean(self):
        # the paging module itself is the one home of the convention
        src = (
            "def page_split(pos, page_size):\n"
            "    return pos // page_size, pos % page_size\n"
        )
        assert lint.lint_source(src, "repro/serving/paging.py") == []
        # calling the helpers, multiplying back to linear positions, and
        # page-count divisibility checks (clean divisor) are all legal
        ok = (
            "from repro.serving import paging\n"
            "def f(pos, page_size, num_pages, n_shards):\n"
            "    pslot, off = paging.page_split(pos, page_size)\n"
            "    lin = pslot * page_size + off\n"
            "    pad = (-num_pages) % n_shards\n"
            "    return lin, pad\n"
        )
        assert lint.lint_source(ok, "repro/models/ok.py") == []

    def test_fed007_scale_arithmetic(self):
        # seeded regression: a consumer dequantizing by hand instead of
        # routing through serving/quant.dequantize (loses the fp8
        # saturation clip and the int8 round semantics)
        src = (
            "def f(codes, k_scales):\n"
            "    return codes.astype('float32') * k_scales[..., None]\n"
        )
        vs = lint.lint_source(src, "repro/models/bad.py")
        assert "FED007" in _rules_of(vs)
        # ...hand-rolled scale computation in a distributed consumer
        src = (
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    kv_scale = jnp.max(jnp.abs(x)) / 127.0\n"
            "    return x / kv_scale\n"
        )
        assert "FED007" in _rules_of(
            lint.lint_source(src, "repro/distributed/bad.py")
        )
        # ...zero-point arithmetic anywhere outside quant.py
        src = "def f(x, zero_point):\n    return x - zero_point\n"
        assert "FED007" in _rules_of(
            lint.lint_source(src, "repro/serving/bad.py")
        )

    def test_fed007_quant_module_and_blessed_idioms_clean(self):
        # the quant module itself is the one home of the codec arithmetic
        src = (
            "def dequantize(codes, scales):\n"
            "    return codes.astype('float32') * scales[..., None]\n"
        )
        assert lint.lint_source(src, "repro/serving/quant.py") == []
        # the softmax sm_scale is unrelated (repo-wide attention idiom) and
        # calling the codec helpers / passing scale leaves around is legal
        ok = (
            "from repro.serving import quant\n"
            "def f(q, dh, sm_scale, pool, scales, new, idx, off):\n"
            "    s = sm_scale if sm_scale is not None else dh**-0.5\n"
            "    qf = q * s\n"
            "    scale = sm_scale * 2.0\n"
            "    pool2, scales2 = quant.paged_write(pool, scales, new, idx, off)\n"
            "    return qf, quant.dequantize(pool2, scales2)\n"
        )
        assert lint.lint_source(ok, "repro/models/ok.py") == []

    def test_repo_is_clean(self):
        import pathlib

        src_root = pathlib.Path(__file__).resolve().parents[1] / "src"
        vs = lint.lint_paths([str(src_root / "repro")], root=str(src_root))
        assert vs == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule} {v.message}" for v in vs
        )


# ---------------------------------------------------------------------------
# jaxpr audit checks: seeded traced fixtures
# ---------------------------------------------------------------------------


class TestAuditChecks:
    def test_f64_regression_caught(self):
        with jax.enable_x64(True):
            t = jax.jit(lambda x: jnp.asarray(x, jnp.float64) * 2).trace(
                jnp.ones(4, jnp.float32)
            )
            issues = jaxpr_audit.audit_traced("fixture", t)
        assert any(i.check == "f64" for i in issues)

    def test_f32_clean(self):
        t = jax.jit(lambda x: x * 2).trace(jnp.ones(4, jnp.float32))
        assert jaxpr_audit.audit_traced("fixture", t) == []

    def test_host_callback_caught(self):
        def f(x):
            jax.debug.callback(lambda v: None, x)
            return x + 1

        t = jax.jit(f).trace(jnp.ones(4))
        issues = jaxpr_audit.audit_traced("fixture", t)
        assert any(i.check == "callback" for i in issues)

    def test_dropped_donation_caught(self):
        # the seeded regression the _donation_for_backend refactor guards
        # against: a serving entry point jitted WITHOUT donating its cache.
        # No accelerator needed — the audit compares declarations.
        t = jax.jit(lambda p, c: (p, c + 1)).trace(jnp.ones(2), jnp.ones(2))
        issues = jaxpr_audit.audit_traced(
            "fixture", t, donate_expected=_donation_for_backend((1,), "tpu")
        )
        assert any(i.check == "donation" for i in issues)
        # ...and on CPU the expectation is empty, so the same jit is clean
        assert (
            jaxpr_audit.audit_traced(
                "fixture", t,
                donate_expected=_donation_for_backend((1,), "cpu"),
            )
            == []
        )

    def test_donated_pytree_operand_is_its_argument_position(self):
        """A donated cache pytree is declared as its argument position, not
        as the flattened indices of its leaves (the form accelerators see:
        the audit compares positions)."""
        params = {"w": jnp.ones(2), "v": jnp.ones(2)}
        cache = {"k": jnp.ones(2), "v": jnp.ones(3)}
        t = jax.jit(lambda p, c: (p, c), donate_argnums=(1,)).trace(params, cache)
        assert jaxpr_audit.donated_argnums(t) == (1,)
        assert jaxpr_audit.audit_traced(
            "fixture", t, donate_expected=_donation_for_backend((1,), "tpu")
        ) == []

    def test_baked_in_buffer_caught(self):
        big = jnp.asarray(np.zeros((600, 600), np.float32))  # > 1 MiB
        t = jax.jit(lambda x: x + big).trace(jnp.ones((600, 600), jnp.float32))
        issues = jaxpr_audit.audit_traced("fixture", t)
        assert any(i.check == "consts" for i in issues)
        # index-vector-scale consts are fine
        small = jnp.arange(64)
        t = jax.jit(lambda x: x + small).trace(jnp.ones(64, jnp.int32))
        assert jaxpr_audit.audit_traced("fixture", t) == []


# ---------------------------------------------------------------------------
# donation policy
# ---------------------------------------------------------------------------


class TestDonationPolicy:
    def test_helper_is_backend_gated(self):
        assert _donation_for_backend((1,), "cpu") == ()
        assert _donation_for_backend((1,), "tpu") == (1,)
        assert _donation_for_backend((0, 1), "gpu") == (0, 1)
        # default backend: this suite runs on CPU
        assert _donation_for_backend((1,)) == ()

    def test_decode_driver_matches_audit_expectation(self):
        """The decode driver's declared donated-operand set must equal what
        the audit derives from the policy helper — i.e. the two donation
        sites in engine.py cannot silently drift from the audited contract."""
        cfg = tiny_config()
        params = build_model(cfg).init(jax.random.key(0))
        eng = FedAttnEngine(cfg, params)
        entries = jaxpr_audit.trace_engine_entries(eng, B=1, L=8, n_new=4)
        backend = jax.default_backend()
        for e in entries:
            declared = jaxpr_audit.donated_argnums(e.traced)
            assert declared == _donation_for_backend(e.cache_argnums, backend), e.name
        # and the audit agrees end-to-end
        assert jaxpr_audit.audit_entries(entries) == []


# ---------------------------------------------------------------------------
# trace guards: executable budgets
# ---------------------------------------------------------------------------


class TestTraceGuard:
    def test_records_distinct_keys(self):
        g = TraceGuard("t", budget=2)
        g.charge("a")
        g.charge("a")  # cache hit — free
        g.charge("b")
        assert g.count == 2

    def test_overrun_raises_only_under_enforce(self):
        g = TraceGuard("t", budget=1)
        g.charge("a")
        g.charge("b")  # records silently outside enforce
        assert g.count == 2
        with trace_guard.enforce():
            with pytest.raises(BudgetExceeded):
                g.charge("c")

    def test_override_tightens(self):
        g = TraceGuard("engine.prefill")  # unbounded by default
        with trace_guard.enforce({"engine.prefill": 1}):
            g.charge("a")
            with pytest.raises(BudgetExceeded):
                g.charge("b")

    def test_scheduler_budget_overrun_caught(self, trace_budget):
        """Seeded regression: rebuilding the resident decode step with a
        second steps_per_admit (≡ a traced arg leaking into the static key)
        must trip the declared budget of 1."""
        from repro.serving.scheduler import ContinuousBatchingScheduler

        cfg = tiny_config()
        params = build_model(cfg).init(jax.random.key(0))
        eng = FedAttnEngine(cfg, params)
        sched = ContinuousBatchingScheduler(eng, max_slots=2, capacity=16)
        with trace_budget():
            sched._step_fn(1)
            sched._step_fn(1)  # same key — cache hit, still within budget
            with pytest.raises(BudgetExceeded):
                sched._step_fn(2)

    def test_engine_compile_counts_backed_by_guards(self):
        cfg = tiny_config()
        params = build_model(cfg).init(jax.random.key(0))
        eng = FedAttnEngine(cfg, params)
        assert eng.compile_counts == {"prefill": 0, "decode": 0}
        eng._prefill_fn(1, 8, 16, None, False)
        assert eng.compile_counts["prefill"] == 1
        assert eng._trace_guards["prefill"].count == 1


# ---------------------------------------------------------------------------
# the repo's own serving surface: every registered arch traces clean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_arch_serving_surface_audits_clean(arch):
    """Trace + audit every jitted serving entry point of every registered
    architecture at reduced size: no f64, no callbacks, donation as
    declared, nothing baked in — and tracing compiles NOTHING."""
    issues = jaxpr_audit.audit_arch(arch)
    assert issues == [], "\n".join(map(str, issues))


def test_audit_traces_without_compiling():
    """The audit's own hygiene: tracing an engine's entry points must leave
    every executable cache empty (eval-shape only, no XLA compilation)."""
    cfg = tiny_config()
    params = build_model(cfg).init(jax.random.key(0))
    eng = FedAttnEngine(cfg, params)
    entries = jaxpr_audit.trace_engine_entries(eng)
    assert len(entries) == 3
    for key, fn in {**eng._prefill_fns, **eng._decode_fns}.items():
        size = jaxpr_audit.executable_cache_size(fn)
        if size is not None:
            assert size == 0, f"tracing compiled executable for {key}"


def test_trace_scaling_is_O_period():
    """Generalized O(period) contract: doubling scan depth keeps every
    entry point's trace flat; a schedule that does not repeat scans its
    whole body as one period, whose trace grows with depth, and the audit
    reports it."""

    def make(periodic):
        def build(k):
            if periodic:
                pattern = (LayerSpec(), LayerSpec(sync=True))
                fed = FedAttnConfig(n_participants=4, sync_interval=2)
            else:  # one sync, at the last layer: one period of 2k layers
                pattern = (LayerSpec(),)
                fed = FedAttnConfig(n_participants=4, sync_interval=2 * k)
            cfg = tiny_config(n_layers=2 * k, pattern=pattern, fedattn=fed)
            params = build_model(cfg).init(jax.random.key(0))
            eng = FedAttnEngine(cfg, params)
            assert eng._plan.n_periods == (k if periodic else 1)
            return eng

        return build

    assert jaxpr_audit.audit_trace_scaling(make(True), depths=(2, 4)) == []
    issues = jaxpr_audit.audit_trace_scaling(make(False), depths=(2, 4))
    assert issues and issues[0].check == "scaling"
