"""Pallas training flash-attention kernels (``kernels/flash_train.py``, in
interpret mode) against the JAX flash path they replace on the TPU and a
naive softmax attention; and the dispatch between the two paths
(``attention.kernel_route``, ``attention.path_counts``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_train
from repro.launch import train
from repro.models import attention as A

def _inputs(b, sq, sk, h, kvh, hd, seed=0):
    """Random q, k, v, dO, rounded to bfloat16 and held in float32, so both
    paths see the same matmul operands."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rnd = lambda key, shape: jax.random.normal(key, shape).astype(  # noqa
        jnp.bfloat16).astype(jnp.float32)
    return (rnd(ks[0], (b, sq, h, hd)), rnd(ks[1], (b, sk, kvh, hd)),
            rnd(ks[2], (b, sk, kvh, hd)), rnd(ks[3], (b, sq, h, hd)))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _kernels(q, k, v, do, kind, window, q_offset):
    """o, lse, (dq, dk, dv) from the kernels: 128-wide tiles, interpreted."""
    kw = dict(kind=kind, window=window, q_offset=q_offset, bq=128, bk=128,
              interpret=True)
    o, lse = flash_train.flash_fwd(q, k, v, **kw)
    return o, lse, flash_train.flash_bwd(q, k, v, o, lse, do, **kw)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _jax_path(q, k, v, do, kind, window, q_offset):
    """o, lse (b, h, sq), (dq, dk, dv) from ``_flash_fwd``/``_flash_bwd``."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sq, kvh, h // kvh, hd)
    out, res = A._flash_fwd(qg, k, v, jnp.zeros((), jnp.float32), kind,
                            window, q_offset, 128, 128)
    do5 = do.reshape(qg.shape).transpose(0, 2, 3, 1, 4)
    dq, dk, dv, _ = A._flash_bwd(kind, window, q_offset, 128, 128, res, do5)
    o = out.transpose(0, 3, 1, 2, 4).reshape(q.shape)
    return o, res[-1].reshape(b, h, sq), (dq.reshape(q.shape), dk, dv)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _naive_vjp(q, k, v, do, kind, window, q_offset):
    o, vjp = jax.vjp(lambda q, k, v: _naive(q, k, v, kind, window, q_offset),
                     q, k, v)
    return o, vjp(do)


def _naive(q, k, v, kind, window, q_offset):
    b, sq, h, hd = q.shape
    g = h // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") * hd ** -.5
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    if kind != "bidir":
        m = kpos <= qpos
        if kind == "swa":
            m &= kpos > qpos - window
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")


def _gap(a, ref):
    """Largest error over the reference's largest magnitude."""
    return float(jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref)))


# (b, sq, sk, h, kvh, hd, kind, window, q_offset); 128-wide tiles, so each
# case spans several q and k tiles, fully visible, edge and skipped ones
CASES = {
    "causal-mha": (1, 256, 256, 4, 4, 64, "causal", 0, 0),
    "swa-kv1": (1, 384, 384, 4, 1, 64, "swa", 200, 0),
    "bidir-cross": (1, 128, 384, 4, 4, 64, "bidir", 0, 0),
    "causal-gqa7": (1, 256, 256, 14, 2, 64, "causal", 0, 0),
    "causal-offset": (1, 256, 384, 2, 2, 128, "causal", 0, 128),
    "swa-offset": (1, 256, 512, 4, 4, 64, "swa", 160, 256),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_jax_path_and_naive(case):
    b, sq, sk, h, kvh, hd, kind, window, off = CASES[case]
    q, k, v, do = _inputs(b, sq, sk, h, kvh, hd)
    o, lse, grads = _kernels(q, k, v, do, kind, window, off)
    assert o.dtype == lse.dtype == jnp.float32
    assert all(g.dtype == jnp.float32 for g in grads)

    ro, rlse, rgrads = _jax_path(q, k, v, do, kind, window, off)
    # the statistics see the same bfloat16 products, summed in float32
    assert _gap(lse.reshape(rlse.shape), rlse) < 1e-5
    # o and the gradients also round p and dS to bfloat16 (one pass, as
    # XLA's DEFAULT precision does on the TPU); the CPU's JAX path keeps them
    # float32
    assert _gap(o, ro) < 1e-2
    for g, rg in zip(grads, rgrads):
        assert _gap(g, rg) < 1e-2

    no, ngrads = _naive_vjp(q, k, v, do, kind, window, off)
    assert _gap(o, no) < 1e-2
    for g, ng in zip(grads, ngrads):
        assert _gap(g, ng) < 1e-2


def test_custom_vjp_routes_grads_through_the_kernels(monkeypatch):
    """``chunked_attention`` on the kernel route (forced here; the kernels
    interpret) gives the JAX route's value and gradients, through all three
    kernels."""
    q, k, v, do = _inputs(1, 256, 256, 4, 2, 64, seed=1)

    def f(q, k, v):
        o, vjp = jax.vjp(lambda q, k, v: A.chunked_attention(
            q, k, v, kind="causal"), q, k, v)
        return o, vjp(do)

    want = jax.jit(lambda *a: f(*a))(q, k, v)
    monkeypatch.setattr(A, "kernel_route", lambda *a: True)
    got = jax.jit(lambda *a: f(*a))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _gap(g, w) < 1e-2
    jaxpr = str(jax.make_jaxpr(f)(q, k, v))
    for kernel in ("flash_train_fwd", "flash_train_dq", "flash_train_dkv"):
        assert kernel in jaxpr


# -- dispatch -----------------------------------------------------------------


@pytest.mark.parametrize("platform,kind,sq,sk,hd,q_offset,g,want", [
    ("tpu", "causal", 2048, 2048, 64, 0, 1, True),     # the benchmark's call
    ("tpu", "causal", 1024, 1024, 64, 0, 1, True),
    ("tpu", "causal", 2048, 2048, 64, 0, 2, True),
    ("tpu", "causal", 4096, 4096, 64, 0, 7, True),     # qwen2-0.5b, long
    ("tpu", "swa", 4096, 4096, 256, 0, 10, True),
    ("tpu", "bidir", 2048, 1024, 64, 0, 1, True),
    ("tpu", "causal", 1024, 2048, 128, 1024, 1, True),  # a prefill chunk
    ("tpu", "causal", 512, 512, 64, 0, 1, False),     # short: the JAX path
    ("tpu", "causal", 512, 512, 64, 0, 7, False),     # qwen2-0.5b bring-up
    ("tpu", "causal", 2048, 2048, 64, 0, 7, False),
    ("tpu", "bidir", 2048, 384, 64, 0, 1, False),     # short keys
    ("tpu", "causal", 2048, 2048, 64, None, 1, False),  # traced offset (CP)
    ("tpu", "causal", 1500, 1500, 64, 0, 1, False),   # length not 128-whole
    ("cpu", "causal", 2048, 2048, 64, 0, 1, False),
    ("tpu", "causal", 2048, 2048, 320, 0, 1, False),  # head_dim over 256
    ("tpu", "causal", 2048, 2048, 36, 0, 1, False),   # head_dim not 8-whole
    ("tpu", "causal", 2048, 2048, 64, 128, 1, False),  # queries past the keys
    ("tpu", "local", 2048, 2048, 64, 0, 1, False),
])
def test_kernel_route(platform, kind, sq, sk, hd, q_offset, g, want):
    assert A.kernel_route(platform, kind, sq, sk, hd, q_offset, g) is want


def test_path_counts_follow_the_route(monkeypatch):
    """Counted when traced: on the CPU every call takes the JAX path; a TPU
    backend sends a static 2048-long causal call to the kernels, and a
    traced offset or a 1500-long call to the JAX path."""
    def shapes(s, h=4):
        return (jax.ShapeDtypeStruct((1, s, h, 64), jnp.float32),) + (
            jax.ShapeDtypeStruct((1, s, 2, 64), jnp.float32),) * 2

    def count(fn, *args):
        before = A.path_counts()
        jax.eval_shape(lambda *a: fn(*a), *args)   # a new trace each time
        after = A.path_counts()
        return {key: after[key] - before[key] for key in after}

    def static(q, k, v):
        s = q.shape[1]
        return A.chunked_attention(q, k, v, chunk_q=s, chunk_k=s)

    def traced(q, k, v):
        s = q.shape[1]
        return A.chunked_attention(q, k, v, chunk_q=s, chunk_k=s,
                                   q_offset=jnp.zeros((), jnp.int32),
                                   static_offset=False)

    assert count(static, *shapes(2048)) == {"kernel": 0, "jax": 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert count(static, *shapes(2048)) == {"kernel": 1, "jax": 0}
    assert count(traced, *shapes(2048)) == {"kernel": 0, "jax": 1}
    assert count(static, *shapes(1500)) == {"kernel": 0, "jax": 1}


def test_jax_path_grads_unchanged_off_the_tpu():
    """Off the TPU ``chunked_attention`` is the JAX flash path as it was:
    its value and gradients equal ``_flash``'s to the bit."""
    b, s, h, kvh, hd = 2, 256, 4, 2, 64
    q, k, v, do = _inputs(b, s, s, h, kvh, hd, seed=2)

    def f(q, k, v):
        return jnp.sum(A.chunked_attention(q, k, v, kind="swa", window=100,
                                           chunk_q=128, chunk_k=128) * do)

    def parent(q, k, v):
        qg = q.reshape(b, s, kvh, h // kvh, hd)
        out = A._flash(qg, k, v, jnp.zeros((), jnp.float32), "swa", 100, 0,
                       128, 128)
        return jnp.sum(out.transpose(0, 3, 1, 2, 4).reshape(q.shape) * do)

    got = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(parent, argnums=(0, 1, 2)))(q, k, v)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_launcher_counts_attention_paths(capsys):
    out = train.run(train.parse_args(
        ["--arch", "qwen2-0.5b", "--reduced", "--steps", "1", "--batch", "2",
         "--seq", "32"]))
    assert out["attention_paths"]["kernel"] == 0
    assert out["attention_paths"]["jax"] >= 1
    assert "on the Pallas kernels" in capsys.readouterr().out
