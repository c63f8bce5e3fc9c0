"""CPU tests of the benchmark at a tiny size.

Run from the checkout root: ``python -m pytest bench/tests``. The tiny
configuration and cells live in a temporary checkout-shaped directory
(``BENCHMARK.json``, ``bench/configs``, ``bench/cells``) that the harness
reads instead of the real one.
"""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CONFIG = {
    "name": "tiny", "source": "test", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4, "vocab_size": 700,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}

TINY_CELL = {
    "participants": 2, "sync_interval": 2, "prompt_len": [24, 60],
    "n_new": [4, 12], "sizes_seed": 1, "max_slots": 4, "capacity": 80,
    "page_size": 8, "steps_per_admit": 2, "backend": None, "warm_width": 4,
    "check": {"requests": 3, "limits": {"gap": 0.03, "lp_err": 0.08}},
}

CELLS = {
    "tiny.closed": {**TINY_CELL, "arrivals": "closed", "clients": 4,
                    "ramp_per_tick": 2, "settle_s": 0.2, "n_sizes": 64},
}


def make_root(path: Path, tied: bool = False, dtype: str = "bfloat16") -> Path:
    (path / "bench" / "configs").mkdir(parents=True)
    (path / "bench" / "cells").mkdir(parents=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": n, "config": "tiny", "traffic": n.split(".")[1], "chips": 1, "why": "test"}
        for n in CELLS
    ]
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = dict(TINY_CONFIG, tie_word_embeddings=tied, torch_dtype=dtype)
    (path / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for n, c in CELLS.items():
        (path / "bench" / "cells" / f"{n}.json").write_text(json.dumps(c))
    return path


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    """Keep the harness's compile cache out of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.getbasetemp() / "jax_cache"))


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
