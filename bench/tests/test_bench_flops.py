"""The Qwen2 FLOP count (``bench/arch/qwen2.py``) against XLA's own count:
``cost_analysis()`` of the compiled forward and backward of a plain
Qwen2-style model (the reference's layers, no recomputation, full s x s
attention scores); and the count the harness finds for each published
configuration by its ``model_type``."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.arch import qwen2 as arch
from bench.reference import qwen2 as ref

ROOT = Path(__file__).resolve().parents[2]

SMALL = {"hidden_size": 256, "intermediate_size": 512,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 4096,
         "rms_norm_eps": 1e-6, "rope_theta": 1e6}


def _plain_loss(params, tokens, labels, c):
    """Forward with no checkpoint anywhere, so the backward is exactly the
    gradient of each op once."""
    x = params["embed"][tokens]
    for i in range(c["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[i], params["unit"]["p0"])
        x = ref.layer(x, w, c)
    x = ref.rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = (x @ params["embed"].T).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    return nll.mean()


@pytest.mark.parametrize("seq", [16, 512])
def test_model_flops_match_xla(seq):
    c = SMALL
    params = jax.eval_shape(arch.make_init(c), jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    grad = jax.jit(jax.grad(lambda p, t, l: _plain_loss(p, t, l, c)))
    xla = grad.lower(params, toks, toks).compile().cost_analysis()["flops"]
    # the plain model computes every score and masks afterwards, so its
    # attention is the non-causal count
    model = seq * (6 * arch.matmul_params(c)
                   + arch.attention_flops_per_token(c, seq, causal=False))
    # XLA also counts the elementwise work the model count leaves out
    # (softmax over the 4096-token vocabulary and over the scores, norms,
    # SiLU, RoPE): under 3% at these widths, and never negative
    assert model <= xla <= 1.03 * model, (xla, model)


def test_causal_attention_counts_the_keys_each_query_sees():
    c, s = SMALL, 2048
    full = arch.attention_flops_per_token(c, s, causal=False)
    assert arch.attention_flops_per_token(c, s) == pytest.approx(
        full * (s + 1) / (2 * s))


@pytest.mark.parametrize("name,seq,params,per_token", [
    # 6 x 463,863,808 matmul weights + 12 x 16 x 64 x 1024.5 x 24 (causal
    # attention at 2048, 302 MFLOP)
    ("qwen1.5-0.5b", 2048, 463_987_712 - 24 * (2 * 1024 + 3 * 1024) - 1024,
     3.0853202e9),
    # 6 x 493,961,216 matmul weights + 12 x 14 x 64 x 256.5 x 24
    ("qwen2-0.5b", 512, 494_032_768 - 24 * (2 * 896 + 896 + 256) - 896,
     3.0299566e9),
])
def test_published_configs(name, seq, params, per_token):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    # the weights of the matmuls: every parameter but the norms and biases
    assert arch.matmul_params(c) == params
    assert arch.train_flops_per_token(c, seq) == pytest.approx(
        per_token, rel=1e-4)


@pytest.mark.parametrize("name,seq,per_token", [
    ("qwen1.5-0.5b", 2048, 3085320192.0),
    ("qwen2-0.5b", 512, 3029956608.0)])
def test_the_harness_finds_each_configurations_count(name, seq, per_token):
    # the very float the count gave when it was the harness's only one, so
    # ``step_mfu`` reads as it did
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    found = harness.model_module(ROOT, c, "arch")
    assert found.train_flops_per_token(c, seq) == per_token
