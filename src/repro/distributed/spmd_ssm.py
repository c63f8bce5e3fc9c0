"""SPMD recurrent layers (RWKV6 / Mamba) with inter-shard state hand-off.

FedAttn generalization for recurrences (DESIGN.md §4):

  * **local layer** — each sequence shard scans its own segment from a zero
    state. Token-shift / causal-conv inputs at the shard start are zero.
    ZERO collectives — the recurrence analogue of Phase-I local attention.
  * **sync layer** — the state crosses shard boundaries: because both WKV6
    and the selective scan are *diagonal-decay linear* recurrences, a
    shard's output decomposes as

        S_out = D_total ⊙ S_in + S_local ,   y = y_local + corr(S_in)

    so we (pass 1) scan locally from zero to get (S_local, D_total),
    (pass 2) all_gather the per-shard summaries, combine prefixes to get
    each shard's true incoming state S_in, and re-run the local scan with
    S_in as the initial state. The collective moves only the per-shard
    state summaries (B·H·dk·dv floats) — the recurrence analogue of the
    KV exchange, and tiny compared to attention's KV gather.

    The 2-pass recompute doubles scan FLOPs at sync layers; replacing it
    with a decay-prefix correction is a logged §Perf optimization.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.distributed import runtime
from repro.kernels import ref as _ref
from repro.kernels.probe import probe_mode


def _rwkv_impl(*args, **kw):
    """Probe mode uses the chunked matrix form (FLOPs-faithful to the
    Pallas kernel, python-looped so cost_analysis counts every chunk)."""
    if probe_mode():
        return _ref.rwkv6_chunked_matrix(*args, **kw)
    return _ref.rwkv6_ref(*args, **kw)


def _prefix_state(states, decays, ax):
    """Incoming state for this shard from gathered per-shard summaries.

    states: (N, B, ..., dk, dv-like) local final states (zero-init scans).
    decays: (N, B, ..., dk[, dv]) total decay factor of each shard, applied
      along the state's decayed dimension.
    Returns S_in for this shard: Σ_{j<i} (Π_{k=j+1..i-1} D_k) ⊙ S_j.
    """
    i = jax.lax.axis_index(ax)
    N = states.shape[0]

    def contrib(j):
        # decay product over shards j+1 .. i-1 (log-space sum for stability)
        ks = jnp.arange(N)
        logd = jnp.log(jnp.maximum(decays, 1e-38))
        mask = ((ks > j) & (ks < i)).astype(logd.dtype)
        total = jnp.exp(jnp.tensordot(mask, logd, axes=(0, 0)))
        return jnp.where(j < i, 1.0, 0.0) * total * states[j]

    return sum(contrib(j) for j in range(N))


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------


def rwkv6_spmd(
    r, k, v, w, u, *, sync: bool, reset_mask=None
):
    """r/k/v/w: (B, L, H, d) with L sharded over the seq axis."""
    ctx = runtime.current()
    assert ctx is not None
    mesh, ax = ctx.mesh, ctx.seq_axis
    spec = P(ctx.bfirst, ax, None, None)

    def local_fn(r, k, v, w):
        y, _ = _rwkv_impl(r, k, v, w, u)
        return y

    def sync_fn(r, k, v, w):
        # pass 1: local scan from zero
        _, S_local = _rwkv_impl(r, k, v, w, u)
        # total decay per k-channel over this shard: exp(Σ_t w_t)
        D_total = jnp.exp(jnp.sum(w.astype(jnp.float32), axis=1))  # (B, H, dk)
        Sg = jax.lax.all_gather(S_local, ax)  # (N, B, H, dk, dv)
        Dg = jax.lax.all_gather(D_total, ax)[..., None]  # (N, B, H, dk, 1)
        S_in = _prefix_state(Sg, Dg, ax)
        # pass 2: re-scan with the true incoming state
        y, _ = _rwkv_impl(r, k, v, w, u, initial_state=S_in)
        return y

    fn = sync_fn if sync else local_fn
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(r, k, v, w)


# ---------------------------------------------------------------------------
# Mamba
# ---------------------------------------------------------------------------


def mamba_spmd(
    x, delta, A, Bm, C, D, *, sync: bool, reset_mask=None
):
    """x/delta: (B, L, d_in); Bm/C: (B, L, d_state) — L sharded on seq axis."""
    ctx = runtime.current()
    assert ctx is not None
    mesh, ax = ctx.mesh, ctx.seq_axis
    s3 = P(ctx.bfirst, ax, None)

    def local_fn(x, delta, Bm, C):
        y, _ = _ref.mamba_scan_ref(x, delta, A, Bm, C, D)
        return y

    def sync_fn(x, delta, Bm, C):
        _, h_local = _ref.mamba_scan_ref(x, delta, A, Bm, C, D)
        # total decay over shard: exp(A ⊙ Σ_t Δ_t) per (d_in, d_state)
        dsum = jnp.sum(delta.astype(jnp.float32), axis=1)  # (B, d_in)
        D_total = jnp.exp(dsum[..., None] * A[None])  # (B, d_in, d_state)
        hg = jax.lax.all_gather(h_local, ax)
        Dg = jax.lax.all_gather(D_total, ax)
        h_in = _prefix_state(hg, Dg, ax)
        y, _ = _ref.mamba_scan_ref(x, delta, A, Bm, C, D, initial_state=h_in)
        return y

    fn = sync_fn if sync else local_fn
    return shard_map(
        fn, mesh=mesh, in_specs=(s3, s3, s3, s3), out_specs=s3,
        check_vma=False,
    )(x, delta, Bm, C)
