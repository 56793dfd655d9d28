"""Pallas TPU kernels for the training and serving hot paths."""
from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret flag: None lets the platform decide (compiled on a
    TPU, the interpreter everywhere else); a bool is taken as given."""
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() != "tpu"
