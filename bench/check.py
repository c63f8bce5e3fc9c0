"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, always with the request that generated the most tokens in
it. For each, the reference (``bench/reference.py``) runs once over the
prompt and the tokens the program served, and at every served token reads
two numbers:

* ``gap``: how far the served token's reference logit lies below the
  reference's best logit at that position. Greedy decoding serves the
  argmax of the program's own logits, so this is 0 where the program and
  the reference agree on the best token, and otherwise the reference's
  margin that the program's rounding crossed. The first served token comes
  from the bucketed admission prefill, the rest from the paged decode step.
* ``lp_err``: how far the program's reported log-probability of the served
  token lies from the reference's.

Each run's number is the widest over all served tokens of the sample. The
control (``quant="fp8"``) reads the same numbers for the token that the
fp8 forward puts first, at the same positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.model import Dims

NAMES = ("gap", "lp_err")


def sample(done: list, rng: np.random.Generator, n: int) -> list:
    """``n`` finished requests drawn from ``rng``, the longest among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_new, r.prompt_len))
    rest = [r for r in done if r is not longest]
    k = min(n - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _served(ref, toks, lps, valid):
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    lp_ref = at - jax.nn.logsumexp(ref, -1)
    gap = jnp.where(valid, best - at, 0.0)
    err = jnp.where(valid, jnp.abs(lps - lp_ref), 0.0)
    return gap.max(), err.max()


@jax.jit
def _control(ref, ctl, valid):
    tok = jnp.argmax(ctl, -1)
    at = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    at_ctl = jnp.take_along_axis(ctl, tok[:, None], -1)[:, 0]
    lp_ref = at - jax.nn.logsumexp(ref, -1)
    lp_ctl = at_ctl - jax.nn.logsumexp(ctl, -1)
    gap = jnp.where(valid, ref.max(-1) - at, 0.0)
    err = jnp.where(valid, jnp.abs(lp_ctl - lp_ref), 0.0)
    return gap.max(), err.max()


def _padded(x: np.ndarray, rows: int, dtype) -> jnp.ndarray:
    out = np.zeros(rows, dtype)
    out[: len(x)] = x
    return jnp.asarray(out)


def readings(w: dict, dm: Dims, reqs: list, *, control: bool = False) -> dict:
    """The widest ``gap`` and ``lp_err`` over the served tokens of
    ``reqs`` (program), or of the control's first tokens at the same
    positions (``control=True``)."""
    gap = err = 0.0
    n_tok = 0
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            L, n = r.prompt_len, r.n_new
            seq = np.concatenate([r.tokens, r.out_tokens[: n - 1].astype(np.int32)])
            ref = reference.logits(w, dm, seq, L, L - 1)
            rows = ref.shape[0]
            valid = jnp.arange(rows) < n
            if control:
                ctl = reference.logits(w, dm, seq, L, L - 1, quant="fp8")
                g, e = _control(ref, ctl, valid)
            else:
                g, e = _served(ref, _padded(r.out_tokens, rows, np.int32),
                               _padded(r.out_logprobs, rows, np.float32), valid)
            # a non-finite reading (NaN logits or logprobs) fails the check
            g, e = (x if np.isfinite(x) else np.inf for x in (float(g), float(e)))
            gap, err = max(gap, g), max(err, e)
            n_tok += n
    return {"gap": gap, "lp_err": err, "tokens": n_tok, "requests": len(reqs)}


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the cell compares (the keys of its ``limits``) beside
    its limit; correct when none exceeds it."""
    ok = read["tokens"] > 0 and all(read[k] <= limits[k] for k in limits)
    # a non-finite reading is printed as null, which JSON can hold
    checks = {k: {"value": read[k] if np.isfinite(read[k]) else None, "limit": limits[k]}
              for k in limits}
    return ok, checks
