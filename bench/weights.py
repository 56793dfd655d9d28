"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference can make the
same ones again from the seed and take nothing from the program.  They are
laid out as ``repro.models.transformer.init_params`` lays out a model whose
layers are all alike (``stack_plan`` gives one unit of one layer, repeated
``L`` times):

    embed                (V, d)          final_norm/scale  (d,)
    unit/p0/norm1/scale  (L, d)          unit/p0/norm2/scale (L, d)
    unit/p0/attn/wq      (L, d, h*hd)    attn/bq (L, h*hd)
    unit/p0/attn/wk, wv  (L, d, kv*hd)   attn/bk, bv (L, kv*hd)
    unit/p0/attn/wo      (L, h*hd, d)
    unit/p0/mlp/w_gate, w_up (L, d, ff)  mlp/w_down (L, ff, d)
    rest                 {}

A norm's ``scale`` is stored as an offset from 1 (the layer multiplies by
1 + scale).  Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2), and
the biases and norm offsets N(0, 0.02^2) and N(0, 0.1^2): not zero, so that
a forward pass that dropped one of them would show against the reference.
``check_layout`` compares this layout with the program's before a run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, c["num_key_value_heads"],
            c.get("head_dim") or d // h, c["intermediate_size"],
            c["vocab_size"])


def jax_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    words = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                   (int(seed) >> 32) & 0xFFFFFFFF, 7])
    return jax.random.PRNGKey(int(words.integers(0, 2**31 - 1)))


def make_init(c: dict, dtype=jnp.float32):
    """-> jitted ``init(key)`` returning the weights in ``dtype``."""
    L, d, h, kv, hd, ff, V = dims(c)
    shapes = {
        "embed": ((V, d), 0.02),
        "final_norm/scale": ((d,), 0.1),
        "norm1/scale": ((L, d), 0.1),
        "norm2/scale": ((L, d), 0.1),
        "wq": ((L, d, h * hd), d ** -0.5),
        "wk": ((L, d, kv * hd), d ** -0.5),
        "wv": ((L, d, kv * hd), d ** -0.5),
        "wo": ((L, h * hd, d), (h * hd) ** -0.5),
        "bq": ((L, h * hd), 0.02),
        "bk": ((L, kv * hd), 0.02),
        "bv": ((L, kv * hd), 0.02),
        "w_gate": ((L, d, ff), d ** -0.5),
        "w_up": ((L, d, ff), d ** -0.5),
        "w_down": ((L, ff, d), ff ** -0.5),
    }

    def init(key):
        keys = jax.random.split(key, len(shapes))
        w = {name: (jax.random.normal(k, shp, jnp.float32) * std
                    ).astype(dtype)
             for k, (name, (shp, std)) in zip(keys, shapes.items())}
        return {
            "embed": w["embed"],
            "final_norm": {"scale": w["final_norm/scale"]},
            "unit": {"p0": {
                "norm1": {"scale": w["norm1/scale"]},
                "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo",
                                           "bq", "bk", "bv")},
                "norm2": {"scale": w["norm2/scale"]},
                "mlp": {n: w[n] for n in ("w_gate", "w_up", "w_down")},
            }},
            "rest": {},
        }

    return jax.jit(init)


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
