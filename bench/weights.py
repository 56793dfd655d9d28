"""What the benchmark's weights share across architectures: a PRNG key from
a seed of any size, the check that an architecture's ``make_init``
(``bench/arch/<model_type>.py``) makes the program's layout, and the
per-layer slices of a weight tree that the comparison reads.

The benchmark makes the weights itself, on the device in one jitted call
from the seed, so that the reference can make the same ones again and take
nothing from the program.  Leaves under ``unit/`` are stacked over layers
(``repro.models.transformer.init_params``): their first axis is the layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    words = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                   (int(seed) >> 32) & 0xFFFFFFFF, 7])
    return jax.random.PRNGKey(int(words.integers(0, 2**31 - 1)))


def check_layout(init, program_template) -> None:
    """Raise unless ``init`` makes the tree, shapes and dtypes that the
    program's own ``init_params`` would (``program_template`` is its
    ``jax.eval_shape``)."""
    ours = jax.eval_shape(init, jax.random.PRNGKey(0))
    a = {jax.tree_util.keystr(p): (x.shape, x.dtype)
         for p, x in jax.tree_util.tree_leaves_with_path(ours)}
    b = {jax.tree_util.keystr(p): (x.shape, x.dtype)
         for p, x in jax.tree_util.tree_leaves_with_path(program_template)}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()), key=str)[:6]
        raise RuntimeError(f"weight layout differs from the program's: {diff}")


def leaf_slices(tree):
    """Names of the per-layer slices of a weight tree, in a fixed order:
    ``unit/p0/attn/wq[3]`` is layer 3's q matrix.  Leaves without a layer
    axis keep their name."""
    names = []
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("unit/"):
            names.extend(f"{name}[{i}]" for i in range(x.shape[0]))
        else:
            names.append(name)
    return names


def slice_norms(tree):
    """Per-layer-slice L2 norms of a weight-shaped tree, as one f32 vector in
    ``leaf_slices`` order (jit-friendly)."""
    out = []
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("unit/"):
            out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)
