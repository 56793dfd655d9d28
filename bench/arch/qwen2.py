"""Qwen2-style decoders (Qwen2, Qwen1.5; ``model_type`` "qwen2"): what the
benchmark knows of the architecture beside its plain reference
(``bench/reference/qwen2.py``).  The harness finds this file by the
configuration's ``model_type``.

- ``model_config(c)``: the program's ``ModelConfig`` for a configuration;
- ``make_init(c, dtype)``: random weights from the seed, in the program's
  layout;
- ``train_flops_per_token(c, seq_len)``: model FLOPs of one trained token.

Weights
-------
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
``bench/weights.check_layout`` compares this layout with the program's
before a run.

FLOPs
-----
A decoder layer (RMSNorm, GQA attention with RoPE, gated SiLU MLP)
multiplies each token's activations by these weights:

    q: d x (h * hd)      k, v: d x (kv * hd) each      o: (h * hd) x d
    gate, up: d x ff     down: ff x d

and the output head multiplies by V x d (tied or not, it is one matmul; the
embedding lookup is a gather and costs no FLOPs).  A matmul with P weights
costs 2P FLOPs per token forward and 4P backward (the gradients of the input
and of the weights), so the weights cost 6 N_mm per trained token, where

    N_mm = L * (d*h*hd + 2*d*kv*hd + h*hd*d + 3*d*ff) + V*d.

Attention adds two matmuls per head per forward: scores q.k over the keys a
query may see, and the weighted sum of values.  Under a causal mask the query
at position i sees i + 1 keys, so over a sequence of s tokens a query sees
(s + 1) / 2 keys on average, and the forward costs 2 * 2 * h * hd * (s+1)/2
FLOPs per token and layer.  The backward costs twice the forward, so

    attention = 3 * 4 * h * hd * (s + 1) / 2 * L    per token.

Norms, RoPE, softmax, SiLU and the loss's softmax are left out: they are
elementwise and come to well under 1% at these widths.  Recomputation under
remat is left out too: it is work the program chooses to redo, not work the
model needs, so the count is the same with remat on or off.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry the file names, with every size the file states put in.  So the
    program runs the file's configuration even where its registry differs
    (qwen1.5-0.5b's ``rope_theta``)."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(c["registry"]),
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c["qkv_bias"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        layer_pattern=None)


def dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, c["num_key_value_heads"],
            c.get("head_dim") or d // h, c["intermediate_size"],
            c["vocab_size"])


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


def matmul_params(c: dict) -> int:
    """N_mm of a configuration file's dict (HF-style keys)."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    kv = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    ff = c["intermediate_size"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return c["num_hidden_layers"] * layer + c["vocab_size"] * d


def attention_flops_per_token(c: dict, seq_len: int, causal: bool = True
                              ) -> float:
    """Forward + backward attention FLOPs per token at ``seq_len``."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    keys = (seq_len + 1) / 2 if causal else seq_len
    return 3 * 4 * h * hd * keys * c["num_hidden_layers"]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token: forward and backward, no
    recomputation, causal attention."""
    return 6 * matmul_params(c) + attention_flops_per_token(c, seq_len)
