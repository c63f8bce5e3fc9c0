"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes whose last two dims are neither 8/128-aligned nor whole, scratch
layouts, VMEM limits). These tests compile each kernel for a *described*
v5e chip — the TPU compiler is installed even where no chip is attached —
at qwen2-7b widths (28 q heads and 4 kv heads of 128, bf16) and check
that the compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as FA
from repro.kernels import flash_decode as FD

NQ, NKV, DH = 28, 4, 128  # qwen2-7b attention widths
SLOTS, PAGE, SLOT_TOKENS = 8, 16, 4096
PAGES = SLOTS * SLOT_TOKENS // PAGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip; keep these
    compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [1, 5])  # decode row, k=4 speculative verify
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
def test_paged_flash_decode_compiles(one_chip, no_persistent_cache, S, pool_dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pp = SLOT_TOKENS // PAGE
    quant = pool_dtype == "int8"
    pool = sds((PAGES, PAGE, NKV, DH), jnp.dtype(pool_dtype))

    def step(q, pk, pv, pages, q_pos, q_seg, kv_seg, *scales):
        return FD.paged_flash_decode(
            q, pk, pv, pages, q_pos=q_pos,
            kv_pos=jnp.arange(pp * PAGE, dtype=jnp.int32),
            q_seg=q_seg, kv_seg=kv_seg, local_only=True,
            k_scales=scales[0] if quant else None,
            v_scales=scales[1] if quant else None,
            interpret=False,
        )

    args = [
        sds((SLOTS, S, NQ, DH), jnp.bfloat16), pool, pool,
        sds((SLOTS, pp), jnp.int32), sds((SLOTS, S), jnp.int32),
        sds((SLOTS, S), jnp.int32), sds((SLOTS, pp * PAGE), jnp.int32),
    ]
    if quant:
        args += [sds((PAGES, NKV), jnp.float32)] * 2
    assert "tpu_custom_call" in _compile_text(step, *args)


@pytest.mark.parametrize("sync", [False, True])  # Phase-I local / Phase-II
def test_flash_attention_prefill_compiles(one_chip, no_persistent_cache, sync):
    """Bucketed admission prefill at L=2048: per-row segment vectors, local
    (Phase I) or sparse-exchange global (Phase II) visibility."""
    B, L = 2, 2048
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def prefill(q, k, v, seg, contributed):
        pos = jnp.arange(L, dtype=jnp.int32)
        return FA.flash_attention(
            q, k, v, q_pos=pos, kv_pos=pos, q_seg=seg, kv_seg=seg,
            local_only=not sync, contributed=contributed if sync else None,
            interpret=False,
        )

    text = _compile_text(
        prefill, sds((B, L, NQ, DH), jnp.bfloat16),
        sds((B, L, NKV, DH), jnp.bfloat16), sds((B, L, NKV, DH), jnp.bfloat16),
        sds((B, L), jnp.int32), sds((B, L), jnp.bool_),
    )
    assert "tpu_custom_call" in text
