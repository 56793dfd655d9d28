"""The faults that the correctness tests plant under the timed path of the
program under test (``src/repro``); the benchmark's own runs plant none.
What the drivers need of one architecture of the program is in
``bench/arch/<model_type>.py``."""
from __future__ import annotations

import contextlib

import numpy as np


def opt_update(plant: str, update):
    """``state_unchanged``: an update that returns params and state as they
    came, so the step leaves its state unchanged."""
    if plant == "state_unchanged":
        return lambda g, s, p, lr: (p, s)
    return update


def half_batch(plant: str, batch: dict, groups: int = 1) -> dict:
    """``half_batch``: a loss mask that leaves out the second half of each
    of ``groups`` equal row groups (a device's rows), or of the tokens of a
    group of one row, so the loss is the mean over the rest."""
    if plant != "half_batch":
        return batch
    rows, seq = batch["tokens"].shape
    per = rows // groups
    mask = np.ones((rows, seq), np.float32)
    for g in range(groups):
        if per > 1:
            mask[g * per + per // 2:(g + 1) * per] = 0.0
        else:
            mask[g, seq // 2:] = 0.0
    return dict(batch, loss_mask=mask)


@contextlib.contextmanager
def collectives(plant: str):
    """``no_exchange``: while the step is traced, the collectives are the
    identity (``all_gather`` gives one device's share), so no device sees
    another's gradient."""
    if plant != "no_exchange":
        yield
        return
    import jax
    saved = jax.lax.psum, jax.lax.pmean, jax.lax.all_gather
    jax.lax.psum = lambda x, axis_name, **_: x
    jax.lax.pmean = lambda x, axis_name, **_: x
    jax.lax.all_gather = lambda x, axis_name, axis=0, tiled=False, **_: (
        x if tiled else jax.numpy.expand_dims(x, axis))
    try:
        yield
    finally:
        jax.lax.psum, jax.lax.pmean, jax.lax.all_gather = saved
