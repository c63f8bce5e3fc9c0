"""Shared fixtures. NOTE: no xla_force_host_platform_device_count here —
tests run on the single real CPU device by design (the multi-device SPMD
equivalence test spawns a subprocess with its own XLA_FLAGS)."""
import os

import jax
import pytest

from repro.analysis import trace_guard
from repro.types import FedAttnConfig, LayerSpec, ModelConfig

# Tier-1 runs under strict dtype promotion: any implicit mixed-strong-dtype
# promotion in src/ (the classic source of silent f64/f32 upcasts on new
# backends) is a hard error instead of a warning. Escape hatch for
# debugging/bisection: REPRO_DTYPE_PROMOTION=standard.
jax.config.update(
    "jax_numpy_dtype_promotion",
    os.environ.get("REPRO_DTYPE_PROMOTION", "strict"),
)


def tiny_config(**kw) -> ModelConfig:
    base = dict(
        name="tiny",
        arch_type="dense",
        n_layers=4,
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        vocab_size=97,
        dtype="float32",
        pattern=tuple(LayerSpec(sync=(i == 3)) for i in range(4)),
        fedattn=FedAttnConfig(n_participants=4, sync_interval=4),
    )
    base.update(kw)
    return ModelConfig(**base)


# The three stack kinds the serving layer must treat uniformly (one
# validity/segment contract from kernels to admission): pure attention,
# jamba-style mamba+attention hybrid, and an attention-free rwkv stack.
# Scheduler/bucket tests sweep these via parametrized fixtures.
STACK_KINDS = ("attn", "hybrid", "rwkv")


def stack_config(kind: str, **kw) -> ModelConfig:
    if kind == "attn":
        return tiny_config(**kw)
    if kind == "hybrid":  # jamba-style mamba+attn interleave
        return tiny_config(
            arch_type="hybrid",
            pattern=(LayerSpec(kind="mamba"), LayerSpec(sync=True)),
            n_layers=4,
            **kw,
        )
    assert kind == "rwkv", kind  # pure-recurrence (attention-free) stack
    return tiny_config(
        arch_type="ssm",
        pattern=tuple(LayerSpec(kind="rwkv", sync=(i == 3)) for i in range(4)),
        rwkv_head_dim=16,
        n_layers=4,
        **kw,
    )


@pytest.fixture
def trace_budget():
    """Enforce executable budgets (repro.analysis.trace_guard) for the test
    body: any jitted serving entry point that builds more distinct
    executables than declared raises BudgetExceeded at the build site.

    Usage::

        def test_churn(trace_budget):
            with trace_budget():                      # declared budgets
                ...
            with trace_budget({"engine.prefill": 2}):  # per-test override
                ...
    """
    return trace_guard.enforce


@pytest.fixture
def rng():
    return jax.random.key(0)


@pytest.fixture
def cfg():
    return tiny_config()
