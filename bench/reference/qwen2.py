"""Plain reference of a Qwen2-style decoder (Qwen2, Qwen1.5; ``model_type``
"qwen2") and its loss: ``weighted_loss``, which ``bench/reference/train.py``
differentiates.

Written from the published architecture (the Hugging Face ``Qwen2Model``),
not from the program, and imports nothing of it.  Per layer:

    h = x + o_proj(attn(rope(q_proj(n1)), rope(k_proj(n1)), v_proj(n1)))
    x' = h + down(silu(gate(n2)) * up(n2))

with n1 = RMSNorm(x), n2 = RMSNorm(h); q, k and v projections with biases;
GQA heads (query head j reads KV head j // (h / kv)); RoPE in the
rotate-half form with inverse frequencies theta^(-2i/hd); causal softmax
attention scaled by hd^-0.5; a final RMSNorm and logits against the tied
embedding.  RMSNorm and softmax statistics, and the loss, are taken in
float32 whatever the storage dtype, as the published code does.

Weights come in the benchmark's layout (``bench/arch/qwen2.py``): a norm's
stored ``scale`` is an offset, so its weight is 1 + scale.

Sizes: the whole (b, h, s, s) score matrix of one layer is made at once,
each layer is recomputed in the backward pass (``jax.checkpoint``), and the
loss runs over blocks of ``LOSS_BLOCK`` token rows, so a 24-layer model at
2 x 2048 tokens fits on one 16 GB chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_BLOCK = 512          # token rows per block of logits


def rms_norm(x, offset, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + offset.astype(jnp.float32))).astype(x.dtype)


def rope(x, theta):
    """x (b, s, heads, hd) -> rotated, rotate-half convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    xf, rotf = x.astype(jnp.float32), rot.astype(jnp.float32)
    return (xf * cos + rotf * sin).astype(x.dtype)


def attention(q, k, v):
    """Causal softmax attention; q (b,s,h,hd), k/v (b,s,kv,hd)."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def layer(x, w, c):
    b, s, d = x.shape
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    eps = c["rms_norm_eps"]
    a = w["attn"]
    n1 = rms_norm(x, w["norm1"]["scale"], eps)
    q = (n1 @ a["wq"] + a["bq"]).reshape(b, s, h, hd)
    k = (n1 @ a["wk"] + a["bk"]).reshape(b, s, kv, hd)
    v = (n1 @ a["wv"] + a["bv"]).reshape(b, s, kv, hd)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    x = x + attention(q, k, v).reshape(b, s, h * hd) @ a["wo"]
    m = w["mlp"]
    n2 = rms_norm(x, w["norm2"]["scale"], eps)
    return x + (jax.nn.silu(n2 @ m["w_gate"]) * (n2 @ m["w_up"])) @ m["w_down"]


def hidden(params, tokens, c):
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, w):
        return layer(x, w, c), None

    x, _ = jax.lax.scan(body, x, params["unit"]["p0"])
    return rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])


def token_nll(params, x, labels):
    """x (n, d) final hidden rows, labels (n,) -> nll (n,) in float32, one
    block of LOSS_BLOCK rows of logits at a time."""
    n, d = x.shape
    blk = min(LOSS_BLOCK, n)
    emb = params["embed"]

    @jax.checkpoint
    def block(_, xs):
        xb, lb = xs
        logits = (xb @ emb.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return None, lse - gold

    _, nll = jax.lax.scan(block, None, (x.reshape(n // blk, blk, d),
                                        labels.reshape(n // blk, blk)))
    return nll.reshape(n)


def row_losses(params, tokens, labels, c):
    """Mean token nll of each row, (b,) float32."""
    b, s = tokens.shape
    x = hidden(params, tokens, c)
    return token_nll(params, x.reshape(b * s, -1),
                     labels.reshape(b * s)).reshape(b, s).mean(axis=1)


def weighted_loss(params, tokens, labels, row_weights, c):
    """sum_b w_b * mean_t nll(b, t): Eqn 4's rate-weighted mean when the
    weights sum to 1."""
    return jnp.sum(row_weights * row_losses(params, tokens, labels, c))
