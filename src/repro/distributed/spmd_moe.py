"""Expert-parallel MoE via shard_map (§Perf iteration 1).

Baseline problem (EXPERIMENTS.md §Perf): letting GSPMD partition the
ragged-dot MoE replicates the full expert weight stack on every device —
qwen3-moe's 1.2 GB/layer of experts got all-gathered 48 times per step,
putting the memory term at ~425 s and useful-FLOPs at 0.01.

Fix — classic expert parallelism on the `model` axis (which already carries
the FedAttn sequence shards, so token gathers ride the same fast axis):

  prefill/train (tokens seq-sharded):
      all_gather(x) over model → every device sees all replica tokens
      → ragged grouped-GEMM over the device's n_experts/16 LOCAL experts
        (tokens routed elsewhere produce zero rows)
      → psum_scatter back to the token shards (each token's combine-sum).
  decode (tokens replicated over model):
      no gather; local-expert ragged GEMM → psum.

Collectives per MoE layer: one (B·L_rep·d) all-gather + one reduce-scatter
— independent of n_experts, vs the baseline's full-weight gathers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.distributed import runtime
from repro.models import moe as M
from repro.types import ModelConfig


def applicable(config: ModelConfig, seq_len: int) -> bool:
    ctx = runtime.current()
    if ctx is None:
        return False
    n_shards = ctx.n_seq_shards
    return (
        config.n_experts > 0
        and config.n_experts % n_shards == 0
        and config.n_shared_experts == 0
    )


def moe_expert_parallel(p, x: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """x: (B, S, d). S sharded over the seq axis when S % shards == 0,
    otherwise treated as replicated (decode)."""
    ctx = runtime.current()
    assert ctx is not None
    mesh, ax = ctx.mesh, ctx.seq_axis
    n_shards = ctx.n_seq_shards
    n_loc = config.n_experts // n_shards
    S = x.shape[1]
    seq_sharded = S % n_shards == 0 and S > 1

    expert_spec = {
        "router": P(None, None),
        "w_gate": P(ax, None, None),
        "w_up": P(ax, None, None),
        "w_down": P(ax, None, None),
    }
    x_spec = P(ctx.bfirst, ax if seq_sharded else None, None)

    def fn(p_loc, x_loc):
        me = jax.lax.axis_index(ax)
        if seq_sharded:
            xg = jax.lax.all_gather(x_loc, ax, axis=1, tiled=True)
        else:
            xg = x_loc
        from repro.kernels.probe import probe_mode

        if probe_mode():
            y = _moe_cost_probe(p_loc, xg, config, n_loc, n_shards)
        else:
            y = M.apply_moe_ragged(
                p_loc, xg, config,
                expert_lo=me * n_loc, n_local_experts=n_loc,
            )
        if seq_sharded:
            return jax.lax.psum_scatter(y, ax, scatter_dimension=1, tiled=True)
        return jax.lax.psum(y, ax)

    p_in = {k: p[k] for k in expert_spec}
    return shard_map(
        fn, mesh=mesh,
        in_specs=(expert_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(p_in, x)


def _moe_cost_probe(p_loc, xg, config, n_loc: int, n_shards: int):
    """FLOPs/bytes-faithful stand-in for the grouped GEMM, used ONLY by the
    roofline cost probe (never executed): this host's XLA lowers
    ``ragged_dot`` as (groups+1) masked full matmuls, inflating
    cost_analysis ~n_loc×; a real TPU grouped GEMM does Σ(group_size)·d·f·2.
    The stand-in runs each local expert's weights over its expected token
    share as a plain dense matmul — identical FLOPs/bytes to the TPU
    kernel under balanced routing, wrong numerics (fine: probes only
    lower+compile)."""
    B, S, d = xg.shape
    k = config.n_experts_per_token
    f = config.expert_d_ff
    T = B * S
    rows_local = max(n_loc, (T * k) // n_shards)
    per_e = max(1, rows_local // n_loc)
    # router cost (real)
    M.route(p_loc, xg, config)
    xf = xg.reshape(T, d)
    reps = (per_e * n_loc + T - 1) // T
    xrep = jnp.concatenate([xf] * reps, axis=0)[: per_e * n_loc]
    pieces = []
    for e in range(n_loc):
        xe = jax.lax.dynamic_slice_in_dim(xrep, e * per_e, per_e, axis=0)
        g = xe @ p_loc["w_gate"][e]
        u = xe @ p_loc["w_up"][e]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
        pieces.append(h @ p_loc["w_down"][e])
    y = jnp.concatenate(pieces, axis=0)
    # fold back to (B, S, d): keep the GEMMs live (no ×0 — XLA would DCE)
    rows = min(per_e * n_loc, T)
    y_used = y[:rows]
    if rows < T:
        y_used = jnp.pad(y_used, ((0, T - rows), (0, 0)))
    return y_used.reshape(B, S, d)
