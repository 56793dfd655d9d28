"""Model FLOPs of one training token, from a configuration's shapes.

A decoder layer of a Qwen2-style model (RMSNorm, GQA attention with RoPE,
gated SiLU MLP) multiplies each token's activations by these weights:

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
