"""Pallas TPU kernel: flash attention forward (online-softmax, VMEM tiles).

The roofline §Perf analysis shows the memory term of every training shape is
dominated by attention score traffic at XLA's CPU fusion boundaries; on TPU
this kernel keeps the (bq x bk) score tile in VMEM so HBM sees only q/k/v/out.
Grid: (batch*q_heads, sq/bq); each program streams KV blocks with a fori_loop
carrying (m, l, acc) — the same math as ``models/attention.py``'s pure-JAX
path, which doubles as this kernel's oracle (GQA handled by the wrapper via
kv-head indexing).  Forward only, at HIGHEST precision, for serving
prefill (``backend="pallas"``); training takes the forward and backward
kernels of ``kernels/flash_train.py``.

Validated in interpret mode on CPU (tests/test_kernels_flash.py) and
compiled for v5e in tests/test_tpu_compile.py; ``interpret=None`` lets the
platform decide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, kind: str, window: int,
                      bk: int, sk: int, scale: float, q_offset: int):
    """q_ref (1, bq, hd); k_ref/v_ref (1, sk, hd); o_ref (1, bq, hd)."""
    _, bq, hd = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    qpos = (q_offset + qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))

    def body(s_idx, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(s_idx * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(s_idx * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_HIGHEST)     # (bq, bk)
        kpos = s_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if kind in ("causal", "swa"):
            mask = kpos <= qpos
            if kind == "swa" and window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_b = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m_b)
        l_b = jnp.sum(p, axis=1, keepdims=True)
        m_new = jnp.maximum(m, m_b)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_b - m_new)
        return (m_new, l * c1 + l_b * c2,
                acc * c1 + jnp.dot(p, v, precision=_HIGHEST) * c2)

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(0, sk // bk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l_f, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "window", "bq", "bk",
                                             "q_offset", "interpret"))
def flash_attention_fwd(q, k, v, *, kind: str = "causal", window: int = 0,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        q_offset: int = 0, interpret: bool = None):
    """q (bh, sq, hd); k/v (bh, sk, hd) — heads pre-flattened/pre-repeated.

    Returns (bh, sq, hd).  bq/bk are the VMEM tile sizes (128-aligned for the
    MXU); KV streams through VMEM one (bk, hd) tile at a time.  ``q_offset``
    shifts query positions for chunked prefill: query row i sits at absolute
    position ``q_offset + i`` relative to the sk keys (static, per-chunk).
    """
    bh, sq, hd = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    grid = (bh, sq // bq)
    kernel = functools.partial(_flash_fwd_kernel, kind=kind, window=window,
                               bk=bk, sk=sk, scale=hd ** -0.5,
                               q_offset=int(q_offset))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, hd), lambda h, i: (h, i, 0)),
                  pl.BlockSpec((1, sk, hd), lambda h, i: (h, 0, 0)),
                  pl.BlockSpec((1, sk, hd), lambda h, i: (h, 0, 0))],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    q_offset: int = 0, interpret: bool = None):
    """Convenience GQA wrapper: q (b, sq, h, hd), k/v (b, sk, kv, hd)."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, sk, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, sk, hd)
    o = flash_attention_fwd(qf, kf, vf, kind=kind, window=window, bq=bq,
                            bk=bk, q_offset=q_offset, interpret=interpret)
    return o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
