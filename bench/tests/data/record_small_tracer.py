"""Records ``tpu_small.xplane.pb``, the trace that ``test_bench_trace.py``
reduces: on one TPU chip, three rounds of an ``input`` span (an add, and a
2 ms host sleep), a ``dispatch`` span that launches two small programs, and
a ``metrics_read`` span that reads both results.

    python3 bench/tests/data/record_small_tracer.py <out_dir>
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
    x = jnp.ones((1024, 1024))
    f(x).block_until_ready()
    g(x).block_until_ready()
    with jax.profiler.trace(out_dir):
        for i in range(3):
            with jax.profiler.TraceAnnotation("input"):
                xx = x + i
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("dispatch"):
                y, z = f(xx), g(xx)
            with jax.profiler.TraceAnnotation("metrics_read"):
                float(y), float(z)


if __name__ == "__main__":
    main(sys.argv[1])
