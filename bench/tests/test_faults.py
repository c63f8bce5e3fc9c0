"""A run whose timed path is broken underneath comes out not correct.

Each test drives the whole run (warm-up, traffic, window, check) at a tiny
size on the CPU, skipping only the look for a chip, with one fault planted
in the program's decode step before the window. A one-chip serving cell
can have two of the faults a check must catch: a step that returns its
state unchanged (the KV pool it was given), and a token altered where it
is produced."""
import time

import pytest

from bench import harness
from conftest import TINY_CONFIG


def _wrap_decode(sched, change):
    make = sched._step_fn

    def patched(n_steps):
        fn = make(n_steps)

        def run(params, cache, *args):
            toks, lps, new_cache = fn(params, cache, *args)
            return change(toks, lps, cache, new_cache)

        return run

    sched._step_fn = patched


def state_unchanged(sched):
    _wrap_decode(sched, lambda toks, lps, old, new: (toks, lps, old))


def token_altered(sched):
    vocab = TINY_CONFIG["vocab_size"]
    _wrap_decode(sched, lambda toks, lps, old, new: ((toks + 1) % vocab, lps, new))


def run(root, workload, fault):
    return harness.run(workload, 4242, 1.5, False, t_process=time.perf_counter(),
                       root=root, require_tpu=False, fault=fault, log=lambda m: None)


def test_sound_run_is_correct(tiny_root):
    assert run(tiny_root, "tiny.closed", None)["correct"]


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_fault_is_not_correct(tiny_root, fault):
    out = run(tiny_root, "tiny.closed", fault)
    assert not out["correct"], out["checks"]
