"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run on a machine with a TPU. It traces a few steps of a small jitted
program between host spans named as the harness names its own
(``bench.window``, ``bench.admit_step``, ``bench.decode_step``,
``bench.bookkeeping``, ``bench.wait``), with host sleeps between them that
leave the device idle, and copies the ``.xplane.pb`` to the given path.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(6):
            name = "bench.admit_step" if i % 3 == 0 else "bench.decode_step"
            with jax.profiler.TraceAnnotation(name):
                for _ in range(4):
                    x = f(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.bookkeeping"):
                time.sleep(0.002)
            if i == 3:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)


if __name__ == "__main__":
    main(sys.argv[1])
