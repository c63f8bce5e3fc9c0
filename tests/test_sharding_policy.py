"""Auto-sharding policy properties (no multi-device needed — specs only)."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import param_spec


@pytest.fixture(scope="module")
def mesh():
    # abstract mesh over the single CPU device is fine for spec generation
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_big_2d_gets_combined_axes(mesh):
    spec = param_spec((4096, 14336), mesh)
    assert ("data", "model") in tuple(spec) or spec == P(("data", "model"), None) \
        or spec[1] == ("data", "model")


def test_small_replicated(mesh):
    assert param_spec((64,), mesh) == P(None)


def test_stacked_leading_protected(mesh):
    spec = param_spec((8, 4096, 4096), mesh, skip_leading=1)
    assert spec[0] is None


@settings(max_examples=60, deadline=None)
@given(
    dims=st.lists(st.sampled_from([1, 16, 63, 64, 128, 255, 256, 768, 1408,
                                   4096, 14336, 49155, 128256]),
                  min_size=1, max_size=4)
)
def test_divisibility_always_respected(mesh, dims):
    """Property: any produced spec only shards dims divisibly."""
    sizes = {"data": 16, "model": 16}
    spec = param_spec(tuple(dims), mesh)
    for d, s in zip(dims, tuple(spec)):
        if s is None:
            continue
        axes = s if isinstance(s, tuple) else (s,)
        n = int(np.prod([sizes[a] for a in axes]))
        assert d % n == 0, (dims, spec)


@settings(max_examples=30, deadline=None)
@given(
    dims=st.lists(st.sampled_from([256, 1024, 4096, 65536]), min_size=2, max_size=3)
)
def test_no_axis_reuse(mesh, dims):
    spec = param_spec(tuple(dims), mesh)
    used = []
    for s in tuple(spec):
        if s is None:
            continue
        used += list(s) if isinstance(s, tuple) else [s]
    assert len(used) == len(set(used)), spec
