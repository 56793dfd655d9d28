"""A test architecture that only the tests' copy of the benchmark holds
(``model_type`` "tiny_swa", copied there as ``bench/arch/tiny_swa.py``): a
Qwen2-style decoder with no q/k/v biases whose every layer attends over a
sliding window of ``sliding_window`` keys, as Mistral's layers do.  It
shows that a configuration with a new ``model_type`` joins the benchmark
through new files alone."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.arch import qwen2


def model_config(c: dict):
    from repro.configs.base import ATTN_SWA, ModelConfig
    L = c["num_hidden_layers"]
    return ModelConfig(
        name=c["name"], family="dense", num_layers=L,
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        layer_pattern=(ATTN_SWA,) * L, window_size=c["sliding_window"],
        rope_theta=float(c["rope_theta"]), tie_embeddings=True,
        norm_eps=c["rms_norm_eps"])


def make_init(c: dict, dtype=jnp.float32):
    """Qwen2's weights without the q/k/v biases."""
    full = qwen2.make_init(c, dtype)

    def init(key):
        w = full(key)
        attn = w["unit"]["p0"]["attn"]
        for b in ("bq", "bk", "bv"):
            del attn[b]
        return w

    return jax.jit(init)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Qwen2's count, with the query at position i seeing min(i + 1, w)
    keys under a window of w."""
    w = min(c["sliding_window"], seq_len)
    keys = (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len
    attention = (3 * 4 * c["num_attention_heads"] * c["head_dim"] * keys
                 * c["num_hidden_layers"])
    return 6 * qwen2.matmul_params(c) + attention
