"""Record the scoped TPU trace that ``test_layers.py`` reduces.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>

Run on a machine with a TPU. A jitted function named ``scoped_step`` runs
a ``lax.fori_loop`` whose body holds two ``jax.named_scope``s named as the
model's (``decode_attention``: two matmuls and a softmax, about eight
times the FLOPs of ``mlp``: one matmul and a tanh). Four steps run under
host spans nested as the harness and the scheduler nest theirs
(``bench.window`` > ``bench.decode_step`` > ``serve.step`` >
``serve.decode.upload`` / ``serve.decode.dispatch`` /
``serve.decode.sync``), with a host sleep in ``serve.decode.upload`` that
leaves the device idle; the ``.xplane.pb`` is copied to the given path.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

TRIPS = 6


@jax.jit
def scoped_step(x, w1, w2):
    def body(i, x):
        with jax.named_scope("decode_attention"):
            s = jax.nn.softmax((x @ w1).astype(jnp.float32), axis=-1)
            h = s.astype(x.dtype) @ w1.T
        with jax.named_scope("mlp"):
            return jnp.tanh(h @ w2) * 0.5

    return jax.lax.fori_loop(0, TRIPS, body, x)


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w1 = (jax.random.normal(k1, (1024, 4096)) * 0.03).astype(jnp.bfloat16)
    w2 = (jax.random.normal(k2, (1024, 1024)) * 0.03).astype(jnp.bfloat16)
    scoped_step(x, w1, w2).block_until_ready()
    d = tempfile.mkdtemp()
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(d)
    with ann("bench.window"):
        for _ in range(4):
            with ann("bench.decode_step"), ann("serve.step"):
                with ann("serve.decode.upload"):
                    time.sleep(0.003)
                with ann("serve.decode.dispatch"):
                    y = scoped_step(x, w1, w2)
                with ann("serve.decode.sync"):
                    np.asarray(y)
            with ann("bench.bookkeeping"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)


if __name__ == "__main__":
    main(sys.argv[1])
