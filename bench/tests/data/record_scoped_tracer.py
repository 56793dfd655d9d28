"""Records ``tpu_scoped.xplane.pb``, the trace that ``test_bench_scopes.py``
reads: on one TPU chip, three rounds of an ``input`` span, a ``dispatch``
span that launches one gradient step of a matmul opened in
``jax.named_scope("attention")`` with its update in
``jax.named_scope("optimizer")``, and a ``metrics_read`` span that reads
the loss.  On a v5e the update fuses into the backward matmul, whose
fusion keeps the matmul's path.

    python3 bench/tests/data/record_scoped_tracer.py <out_dir>
"""
import sys

import jax
import jax.numpy as jnp


def loss(w, x):
    with jax.named_scope("attention"):
        y = x @ w
    return jnp.tanh(y).sum()


@jax.jit
def step(w, x):
    value, grad = jax.value_and_grad(loss)(w, x)
    with jax.named_scope("optimizer"):
        w = w - 1e-3 * grad
    return w, value


def main(out_dir: str) -> None:
    w = jnp.full((1024, 1024), 1e-3)
    x = jnp.ones((1024, 1024))
    jax.block_until_ready(step(w, x))
    with jax.profiler.trace(out_dir):
        for i in range(3):
            with jax.profiler.TraceAnnotation("input"):
                xx = x + i
            with jax.profiler.TraceAnnotation("dispatch"):
                w, value = step(w, xx)
            with jax.profiler.TraceAnnotation("metrics_read"):
                float(value)


if __name__ == "__main__":
    main(sys.argv[1])
