"""The plain reference against the program's own serving path, at a tiny
size in float32 on the CPU: a run of the tiny cell through the harness
(bucketed coalesced admission prefill, paged pool, fused decode steps) is
compared with the reference over the served tokens."""
import time

import pytest

from bench import harness
from conftest import make_root


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_reference_matches_the_served_path_in_float32(tmp_path, tied):
    root = make_root(tmp_path, tied=tied, dtype="float32")
    out = harness.run("tiny.closed", 2**33 + 17, 1.5, False, t_process=time.perf_counter(),
                      root=root, require_tpu=False, log=lambda m: None)
    checks = out["checks"]
    # float32 on both sides: the served tokens are the reference's argmax
    # (a near-tie could flip only below float32 rounding) and the reported
    # log-probabilities agree to float32 rounding over a few layers
    assert checks["gap"]["value"] < 1e-4
    assert checks["lp_err"]["value"] < 1e-4
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["output_tok_s"]["value"] > 0
