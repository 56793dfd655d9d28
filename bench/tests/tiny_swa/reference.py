"""Plain reference of the test architecture ``tiny_swa`` (``arch.py``
beside it), copied into the tests' copy of the benchmark as
``bench/reference/tiny_swa.py``: Qwen2's layer without the q/k/v biases,
each query attending to itself and the ``sliding_window`` - 1 keys before
it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import qwen2 as base


def attention(q, k, v, window):
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    i = jnp.arange(s)
    seen = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def layer(x, w, c):
    b, s, d = x.shape
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a, m = w["attn"], w["mlp"]
    n1 = base.rms_norm(x, w["norm1"]["scale"], eps)
    q = base.rope((n1 @ a["wq"]).reshape(b, s, h, hd), theta)
    k = base.rope((n1 @ a["wk"]).reshape(b, s, kv, hd), theta)
    v = (n1 @ a["wv"]).reshape(b, s, kv, hd)
    o = attention(q, k, v, c["sliding_window"]).reshape(b, s, h * hd)
    x = x + o @ a["wo"]
    n2 = base.rms_norm(x, w["norm2"]["scale"], eps)
    return x + (jax.nn.silu(n2 @ m["w_gate"]) * (n2 @ m["w_up"])) @ m["w_down"]


def weighted_loss(params, tokens, labels, row_weights, c):
    b, s = tokens.shape
    x, _ = jax.lax.scan(lambda x, w: (layer(x, w, c), None),
                        params["embed"][tokens], params["unit"]["p0"])
    x = base.rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    nll = base.token_nll(params, x.reshape(b * s, -1), labels.reshape(b * s))
    return jnp.sum(row_weights * nll.reshape(b, s).mean(axis=1))
