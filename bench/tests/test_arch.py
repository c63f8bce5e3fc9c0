"""A second architecture goes in as a module of its own, found by the
configuration's ``model_type``, and nothing else of the benchmark changes.

The module here is registered under ``bench.arch`` by the test alone: a
Qwen2 variant with no q/k/v bias and with RMSNorm over each head of q and
k before RoPE (as Qwen3 has), whose weights carry keys of its own. The
tiny closed cell runs through ``harness.run`` in float32 on the CPU and
the served tokens match the reference, which runs the module's layer."""
import functools
import json
import sys
import time
import types
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, model
from bench.arch import qwen2
from bench.reference import attend, mm, rms
from conftest import make_root

NAME = "qk_norm_variant"
KEYS = ("attn_norm", "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm",
        "mlp_norm", "gate", "up", "down")


def dims(cfg, participants, sync_interval):
    return replace(qwen2.dims(cfg, participants, sync_interval), model_type=NAME)


def program_config(cfg, dm):
    return replace(qwen2.program_config(cfg, dm), qkv_bias=False, qk_norm=True)


def draw(key, dm):
    dt = jnp.dtype(dm.dtype)
    P, d, f, dh = dm.n_periods, dm.d, dm.d_ff, dm.d_head
    q, kv = dm.n_q * dh, dm.n_kv * dh
    keys = iter(jax.random.split(key, 3 + len(KEYS) * dm.sync_interval))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)

    layers = [{
        "attn_norm": scale((P, d)), "q_proj": normal((P, d, q), d ** -0.5),
        "k_proj": normal((P, d, kv), d ** -0.5), "v_proj": normal((P, d, kv), d ** -0.5),
        "o_proj": normal((P, q, d), q ** -0.5), "q_norm": scale((P, dh)),
        "k_norm": scale((P, dh)), "mlp_norm": scale((P, d)),
        "gate": normal((P, d, f), d ** -0.5), "up": normal((P, d, f), d ** -0.5),
        "down": normal((P, f, d), f ** -0.5),
    } for _ in range(dm.sync_interval)]
    return {"embed": normal((dm.vocab_rows, d), d ** -0.5), "final_ln": scale((d,)),
            "head": normal((d, dm.vocab_rows), d ** -0.5), "layers": layers}


def program_params(w, dm):
    return {
        "embed": {"tok": w["embed"]},
        "stacked": [{
            "norm1": {"scale": s["attn_norm"]},
            "attn": {"wq": s["q_proj"], "wk": s["k_proj"], "wv": s["v_proj"],
                     "wo": s["o_proj"], "q_norm": s["q_norm"], "k_norm": s["k_norm"]},
            "norm2": {"scale": s["mlp_norm"]},
            "ffn": {"w_gate": s["gate"], "w_up": s["up"], "w_down": s["down"]},
        } for s in w["layers"]],
        "remainder": [],
        "final_norm": {"scale": w["final_ln"]},
        "head": {"w": w["head"]},
    }


@functools.partial(jax.jit, static_argnames=("dm", "sync", "quant"))
def _layer(x, lw, pos, seg, *, dm, sync, quant):
    T, dh = x.shape[0], dm.d_head
    h = rms(x, lw["attn_norm"], dm.eps)
    q = rms(mm(h, lw["q_proj"], quant).reshape(T, dm.n_q, dh), lw["q_norm"], dm.eps)
    k = rms(mm(h, lw["k_proj"], quant).reshape(T, dm.n_kv, dh), lw["k_norm"], dm.eps)
    v = mm(h, lw["v_proj"], quant).reshape(T, dm.n_kv, dh)
    q, k = qwen2.rope(q, pos, dm.theta), qwen2.rope(k, pos, dm.theta)
    x = x + mm(attend(q, k, v, pos, seg, sync=sync), lw["o_proj"], quant)
    h = rms(x, lw["mlp_norm"], dm.eps)
    return x + mm(jax.nn.silu(mm(h, lw["gate"], quant)) * mm(h, lw["up"], quant),
                  lw["down"], quant)


def layer(x, lw, pos, seg, *, dm, index, quant):
    return _layer(x, lw, pos, seg, dm=dm, sync=dm.is_sync(index), quant=quant)


def variant():
    mod = types.ModuleType(f"bench.arch.{NAME}")
    mod.__dict__.update(dims=dims, program_config=program_config, draw=draw,
                        program_params=program_params, layer=layer,
                        weight_flops=qwen2.weight_flops, pair_flops=qwen2.pair_flops)
    return mod


@pytest.fixture
def registered(monkeypatch):
    mod = variant()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_second_architecture_serves_and_matches_its_reference(tmp_path, registered):
    root = make_root(tmp_path, dtype="float32")
    cfg_path = root / "bench" / "configs" / "tiny.json"
    cfg = model.load_json(cfg_path)
    cfg_path.write_text(json.dumps(dict(cfg, model_type=NAME)))
    dm = model.dims(dict(cfg, model_type=NAME), 2, 2)
    assert dm.model_type == NAME
    w = model.make_weights(dm, 5)
    assert set(w["layers"][0]) == set(KEYS)
    assert not set(KEYS) & set(model.make_weights(replace(dm, model_type="qwen2"), 5)["layers"][0])

    out = harness.run("tiny.closed", 2**32 + 3, 1.5, False, t_process=time.perf_counter(),
                      root=root, require_tpu=False, log=lambda m: None)
    checks = out["checks"]
    assert checks["gap"]["value"] < 1e-4
    assert checks["lp_err"]["value"] < 1e-4
    assert out["correct"] and out["attempted"] > 0


def test_unknown_model_type_is_refused_by_its_missing_file():
    cfg = {"model_type": "no_such_architecture"}
    with pytest.raises(harness.Refused, match="bench/arch/no_such_architecture.py"):
        model.dims(cfg, 2, 2)
    with pytest.raises(harness.Refused, match="names no module"):
        model.dims({"model_type": "../qwen2"}, 2, 2)


def test_a_module_may_count_its_own_flops(registered):
    dm = qwen2.Dims(model_type=NAME, n_layers=4, d=8, n_q=2, n_kv=1, d_head=4, d_ff=16,
                    vocab=10, vocab_rows=10, tied=False, eps=1e-6, theta=1e4,
                    dtype="float32", sync_interval=2, participants=2)
    plain = replace(dm, model_type="qwen2")
    assert flops.prefill_flops(dm, 6) == flops.prefill_flops(plain, 6)
    assert flops.decode_flops(dm, 6, 0, 3) == flops.decode_flops(plain, 6, 0, 3)
    registered.prefill_flops = lambda dm, L: 7 * L
    registered.decode_flops = lambda dm, L, lo, hi: 3 * (hi - lo)
    assert flops.prefill_flops(dm, 6) == 42
    assert flops.decode_flops(dm, 6, 0, 3) == 9
