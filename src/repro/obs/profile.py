"""JAX profiler capture windows around the hot paths.

* :func:`capture` — context manager: a ``jax.profiler`` trace written under
  ``logdir`` while the body runs.  A profiler that cannot start raises: a
  run asked to be traced that ran untraced would leave a reader of its
  trace nothing to read, and no sign why.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator


@contextlib.contextmanager
def capture(logdir: str) -> Iterator[None]:
    """Profiler capture window writing an ``.xplane.pb`` under ``logdir``
    (created if missing).  Exceptions from the profiler and from the body
    propagate."""
    import jax.profiler
    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        yield
