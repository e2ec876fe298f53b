"""Record ``cpu_window.xplane.pb``, the small trace the reduction test reads.

    JAX_PLATFORMS=cpu python3 tests/bench/data/record_cpu_trace.py

A ``bench:window`` span holds three jitted loops (each in a
``bench:solve`` span) and a 200 ms ``bench:sleep`` in which nothing
runs, so that the longest idle stretch belongs to that span.
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@jax.jit
def loop(x):
    return jax.lax.fori_loop(0, 20, lambda i, x: jnp.sin(x) + x[::-1], x)


def main():
    x = jnp.ones((1 << 16,))
    loop(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench:window"):
        for _ in range(3):
            with TraceAnnotation("bench:solve"):
                loop(x).block_until_ready()
        with TraceAnnotation("bench:sleep"):
            time.sleep(0.2)
        with TraceAnnotation("bench:solve"):
            loop(x).block_until_ready()
    jax.profiler.stop_trace()
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, Path(__file__).with_name("cpu_window.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
