"""Attention: flash-style chunked softmax attention.

Execution modes (DESIGN.md §5):

* ``chunked_attention`` — local (per-shard) attention.  On the TPU a call
  that ``kernel_route`` accepts (static offset; causal, swa or bidir;
  lengths in multiples of 128 and long enough for the GQA ratio; head_dim a
  multiple of 8 up to 256) runs the Pallas forward, dQ and dK/dV kernels of
  ``kernels/flash_train.py`` under the custom VJP ``_flash_kernel``.  Every
  other call — off the TPU, traced offsets (context parallel), odd or short
  lengths — takes the JAX path: the query axis is blocked by a static
  Python loop so causal/SWA layers statically skip fully-masked KV blocks
  (sub-quadratic for SWA); each query block runs an online-softmax
  ``lax.scan`` over its KV blocks, so ``s_q x s_k`` scores are never
  materialised.  Both paths are **custom VJPs** that recompute
  block scores in the backward pass (saving only out + logsumexp), otherwise
  jax's scan autodiff stashes every block's probability matrix —
  O(s_q*s_k) — which is exactly the memory wall flash attention exists to
  avoid.  The JAX path's rules are the kernels' oracle.
* ``context_parallel_attention`` — shard_map over the tensor axis for archs
  whose head count does not divide the 16-way model axis: queries stay
  sequence-sharded, K/V are all-gathered, block skipping degrades to masking
  (positions arrive as a traced array).
* ``decode_attention`` — single-token attention against a (possibly
  sequence-sharded) KV cache; softmax statistics reduce across shards via the
  partitioner.

Softmax statistics accumulate in fp32 regardless of the compute dtype.
KV positions inside scans derive from the loop counter (never precomputed
xs — XLA would hoist per-iteration masks into stacked buffers).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _extent(kind: str, q_lo: int, q_hi: int, sk: int, window: int,
            chunk_k: int, static_offset: bool) -> Tuple[int, int]:
    """Static KV block range for queries [q_lo, q_hi) (global positions)."""
    if kind in ("causal", "swa") and static_offset:
        k_hi = min(sk, q_hi)
        k_lo = 0
        if kind == "swa" and window > 0:
            k_lo = max(0, q_lo - window + 1)
        k_lo = (k_lo // chunk_k) * chunk_k
        k_hi = -(-k_hi // chunk_k) * chunk_k
        k_hi = max(min(k_hi, sk), k_lo + chunk_k)
        return k_lo, k_hi
    return 0, sk


def _mask(kind: str, qpos, kpos, window: int):
    if kind not in ("causal", "swa"):
        return None
    m = kpos[None, :] <= qpos[:, None]
    if kind == "swa" and window > 0:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _scores(qb, kb, qpos, kpos, kind, window):
    """qb (b,qc,kv,g,hd), kb (b,kc,kv,hd) -> s (b,kv,g,qc,kc) fp32.

    fp32 via preferred_element_type (NOT .astype on the result: XLA rewrites
    convert(dot(a,b)) into dot(convert(a), convert(b)) and then hoists the
    operand converts out of scan loops — materialising fp32 copies of whole
    K/V stacks)."""
    scale = qb.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    m = _mask(kind, qpos, kpos, window)
    if m is not None:
        s = jnp.where(m[None, None, None], s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward / backward over one query chunk


def _fwd_qchunk(qb, k, v, qpos0, k_lo, k_hi, kind, window, chunk_k):
    """qb (b,qc,kv,g,hd); returns (o (b,kv,g,qc,hd) f32, lse (b,kv,g,qc))."""
    b, qc, kvh, g, hd = qb.shape
    kb = jax.lax.slice_in_dim(k, k_lo, k_hi, axis=1)
    vb = jax.lax.slice_in_dim(v, k_lo, k_hi, axis=1)
    n_blocks = (k_hi - k_lo) // chunk_k
    kb = kb.reshape(b, n_blocks, chunk_k, kvh, hd).swapaxes(0, 1)
    vb = vb.reshape(b, n_blocks, chunk_k, kvh, hd).swapaxes(0, 1)
    qpos = qpos0 + jnp.arange(qc)

    def step(carry, inp):
        m, l, acc, blk = carry
        kb_i, vb_i = inp
        kpos_i = k_lo + blk * chunk_k + jnp.arange(chunk_k)
        s = _scores(qb, kb_i, qpos, kpos_i, kind, window)
        m_b = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_b[..., None])
        l_b = jnp.sum(p, axis=-1)
        o_b = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vb_i.dtype), vb_i
                         ).astype(jnp.float32)
        m_new = jnp.maximum(m, m_b)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_b - m_new)
        return (m_new, l * c1 + l_b * c2,
                acc * c1[..., None] + o_b * c2[..., None], blk + 1), None

    m0 = jnp.full((b, kvh, g, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, qc), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, qc, hd), jnp.float32)
    carry0 = (m0, l0, a0, jnp.zeros((), jnp.int32))
    if n_blocks == 1:
        (m_f, l_f, acc, _), _ = step(carry0, (kb[0], vb[0]))
    else:
        (m_f, l_f, acc, _), _ = jax.lax.scan(step, carry0, (kb, vb))
    l_safe = jnp.maximum(l_f, 1e-30)
    return acc / l_safe[..., None], m_f + jnp.log(l_safe)


def _bwd_qchunk(qb, k, v, o, lse, do, qpos0, k_lo, k_hi, kind, window,
                chunk_k):
    """Flash backward for one q chunk; recomputes scores per KV block.

    Returns (dq (b,qc,kv,g,hd), dk_part (b,k_hi-k_lo,kv,hd), dv_part).
    o/do (b,kv,g,qc,hd) f32; lse (b,kv,g,qc).
    """
    b, qc, kvh, g, hd = qb.shape
    scale = hd ** -0.5
    kb = jax.lax.slice_in_dim(k, k_lo, k_hi, axis=1)
    vb = jax.lax.slice_in_dim(v, k_lo, k_hi, axis=1)
    n_blocks = (k_hi - k_lo) // chunk_k
    kb = kb.reshape(b, n_blocks, chunk_k, kvh, hd).swapaxes(0, 1)
    vb = vb.reshape(b, n_blocks, chunk_k, kvh, hd).swapaxes(0, 1)
    qpos = qpos0 + jnp.arange(qc)
    D = jnp.sum(do * o, axis=-1)                      # (b,kv,g,qc)
    qf = qb.astype(jnp.float32)

    def step(carry, inp):
        dq, blk = carry
        kb_i, vb_i = inp
        kpos_i = k_lo + blk * chunk_k + jnp.arange(chunk_k)
        s = _scores(qb, kb_i, qpos, kpos_i, kind, window)
        p = jnp.exp(s - lse[..., None])               # (b,kv,g,qc,kc)
        kf = kb_i.astype(jnp.float32)
        vf = vb_i.astype(jnp.float32)
        dv_i = jnp.einsum("bkgqs,bkgqd->bskd", p, do)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do, vf)
        ds = p * (dp - D[..., None]) * scale
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, kf)
        dk_i = jnp.einsum("bkgqs,bqkgd->bskd", ds, qf)
        return (dq, blk + 1), (dk_i, dv_i)

    dq0 = jnp.zeros((b, qc, kvh, g, hd), jnp.float32)
    carry0 = (dq0, jnp.zeros((), jnp.int32))
    if n_blocks == 1:
        (dq, _), (dk_b, dv_b) = step(carry0, (kb[0], vb[0]))
        dk_b, dv_b = dk_b[None], dv_b[None]
    else:
        (dq, _), (dk_b, dv_b) = jax.lax.scan(step, carry0, (kb, vb))
    dk_part = dk_b.swapaxes(0, 1).reshape(b, k_hi - k_lo, kvh, hd)
    dv_part = dv_b.swapaxes(0, 1).reshape(b, k_hi - k_lo, kvh, hd)
    return dq, dk_part, dv_part


# ---------------------------------------------------------------------------
# custom-vjp flash attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, qpos_base, kind: str, window: int, q_offset: Optional[int],
           chunk_q: int, chunk_k: int):
    out, _ = _flash_fwd(q, k, v, qpos_base, kind, window, q_offset, chunk_q,
                        chunk_k)
    return out


def _flash_fwd(q, k, v, qpos_base, kind, window, q_offset, chunk_q, chunk_k):
    """q (b,sq,kv,g,hd) pre-grouped; qpos_base: fp32 scalar array (traced
    global offset, CP mode) — ignored when q_offset is a static int."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    static = q_offset is not None
    outs, lses = [], []
    for q0 in range(0, sq, chunk_q):
        qb = jax.lax.slice_in_dim(q, q0, q0 + chunk_q, axis=1)
        if static:
            qpos0 = q_offset + q0
            k_lo, k_hi = _extent(kind, q_offset + q0, q_offset + q0 + chunk_q,
                                 sk, window, chunk_k, True)
        else:
            qpos0 = qpos_base.astype(jnp.int32) + q0
            k_lo, k_hi = 0, sk
        o, lse = _fwd_qchunk(qb, k, v, qpos0, k_lo, k_hi, kind, window,
                             chunk_k)
        outs.append(o)
        lses.append(lse)
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    lse = jnp.concatenate(lses, axis=3) if len(lses) > 1 else lses[0]
    return out.astype(q.dtype), (q, k, v, qpos_base, out.astype(q.dtype), lse)


def _flash_fwd_rule(q, k, v, qpos_base, kind, window, q_offset, chunk_q,
                    chunk_k):
    with jax.named_scope("attention"):
        return _flash_fwd(q, k, v, qpos_base, kind, window, q_offset,
                          chunk_q, chunk_k)


def _flash_bwd_rule(kind, window, q_offset, chunk_q, chunk_k, res, dout):
    with jax.named_scope("attention"):
        return _flash_bwd(kind, window, q_offset, chunk_q, chunk_k, res, dout)


def _flash_bwd(kind, window, q_offset, chunk_q, chunk_k, res, dout):
    q, k, v, qpos_base, out, lse = res
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    static = q_offset is not None
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for q0 in range(0, sq, chunk_q):
        qb = jax.lax.slice_in_dim(q, q0, q0 + chunk_q, axis=1)
        ob = jax.lax.slice_in_dim(out, q0, q0 + chunk_q, axis=3
                                  ).astype(jnp.float32)
        dob = jax.lax.slice_in_dim(dout, q0, q0 + chunk_q, axis=3
                                   ).astype(jnp.float32)
        lseb = jax.lax.slice_in_dim(lse, q0, q0 + chunk_q, axis=3)
        if static:
            qpos0 = q_offset + q0
            k_lo, k_hi = _extent(kind, q_offset + q0, q_offset + q0 + chunk_q,
                                 sk, window, chunk_k, True)
        else:
            qpos0 = qpos_base.astype(jnp.int32) + q0
            k_lo, k_hi = 0, sk
        dq_c, dk_p, dv_p = _bwd_qchunk(qb, k, v, ob, lseb, dob, qpos0, k_lo,
                                       k_hi, kind, window, chunk_k)
        dq = dq.at[:, q0:q0 + chunk_q].set(dq_c)
        dk = dk.at[:, k_lo:k_hi].add(dk_p)
        dv = dv.at[:, k_lo:k_hi].add(dv_p)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros((), jnp.float32))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# the same rules as Pallas kernels (TPU only; ``kernel_route`` decides)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_kernel(q, k, v, kind: str, window: int, q_offset: int):
    return _flash_kernel_fwd(q, k, v, kind, window, q_offset)[0]


def _flash_kernel_fwd(q, k, v, kind, window, q_offset):
    """q (b,sq,h,hd), k/v (b,sk,kv,hd); saves (q, k, v, o, lse)."""
    from repro.kernels.flash_train import flash_fwd
    with jax.named_scope("attention"):
        o, lse = flash_fwd(q, k, v, kind=kind, window=window,
                           q_offset=q_offset)
    return o, (q, k, v, o, lse)


def _flash_kernel_bwd(kind, window, q_offset, res, dout):
    from repro.kernels.flash_train import flash_bwd
    with jax.named_scope("attention"):
        return flash_bwd(*res, dout, kind=kind, window=window,
                         q_offset=q_offset)


_flash_kernel.defvjp(_flash_kernel_fwd, _flash_kernel_bwd)

# attention calls traced so far, by the path each took
_PATH_COUNTS = {"kernel": 0, "jax": 0}


def path_counts() -> dict:
    """How many ``chunked_attention`` calls took the Pallas kernels and how
    many the JAX path, counted when traced (a layer scan traces its body
    once, however many layers it runs)."""
    return dict(_PATH_COUNTS)


def kernel_route(platform: str, kind: str, sq: int, sk: int, hd: int,
                 q_offset, g: int) -> bool:
    """Whether an attention call takes the Pallas training kernels
    (``kernels/flash_train.py``).  They can: on the TPU, with a static
    ``q_offset`` (None when traced) that puts each query of a causal or SWA
    call at or after key 0 and at most at the last key, a causal, swa or
    bidir mask, lengths in multiples of 128, and head_dim a multiple of 8
    up to 256.  They pay: where both lengths reach ``KERNEL_MIN_LEN``
    times ``g`` (query heads per KV head) capped at 4.  Everything else
    takes the JAX path."""
    if platform != "tpu" or q_offset is None:
        return False
    if kind not in ("causal", "swa", "bidir"):
        return False
    if kind != "bidir" and not 0 <= q_offset <= sk - sq:
        return False
    if sq % 128 or sk % 128 or hd % 8 or hd > 256:
        return False
    return min(sq, sk) >= KERNEL_MIN_LEN * min(g, 4)


# Part of the JAX path's cost grows with the KV heads (its per-chunk K/V
# slices and dK/dV updates), and past 2048 it grows faster than the square
# of the length; a kernel grid step costs about as much at 512 as at 2048.
# Kernel over JAX time of a layer's forward, remat forward and backward,
# causal, head_dim 64, on a v5e (16 query heads unless noted; g7 is 14/2):
#
#   length   g1     g2     g4     g7     g8
#   512      1.19   1.66   1.71   1.80   1.81
#   1024     0.84   1.27   1.45
#   2048     0.59   0.89   1.02   1.41   1.17
#   4096     0.13   0.26   0.38   0.66   0.56
#   8192                          0.56   0.47
KERNEL_MIN_LEN = 1024


# ---------------------------------------------------------------------------
# public entry points


def chunked_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                      q_offset=0, chunk_q: int = 512, chunk_k: int = 512,
                      static_offset: bool = True, backend: str = "jax",
                      interpret: Optional[bool] = None):
    """q (b, sq, h, hd); k/v (b, sk, kv, hd) -> (b, sq, h, hd).

    ``q_offset``: global position of q[0] relative to k[0].  Python int (+
    ``static_offset``) enables static skipping of fully-masked KV blocks; a
    traced offset (context parallel) falls back to mask-only.

    ``backend="pallas"`` routes the forward through the Pallas flash kernel
    (``kernels/flash_attention.py``, forward-only — serving prefill).  Traced
    offsets (context parallel) always take the JAX path; ``interpret`` is
    the Pallas interpret override (None = autodetect: interpret off-TPU).
    Otherwise the call takes the training kernels where ``kernel_route``
    says it can, and the JAX path elsewhere.
    """
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    import math
    if backend == "pallas" and static_offset:
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(
            q, k, v, kind=kind, window=window, q_offset=int(q_offset),
            bq=math.gcd(sq, 128), bk=math.gcd(sk, 128),
            interpret=interpret)
    static = q_offset if static_offset else None
    if kernel_route(jax.default_backend(), kind, sq, sk, hd, static, g):
        _PATH_COUNTS["kernel"] += 1
        return _flash_kernel(q, k, v, kind, window, int(q_offset))
    _PATH_COUNTS["jax"] += 1
    qg = q.reshape(b, sq, kvh, g, hd)
    # snap chunks to divisors of the sequence lengths (e.g. whisper's 1536
    # frames with a 1024 default -> gcd 512)
    chunk_q = math.gcd(min(chunk_q, sq), sq)
    chunk_k = math.gcd(min(chunk_k, sk), sk)
    assert sq % chunk_q == 0 and sk % chunk_k == 0, (sq, chunk_q, sk, chunk_k)
    if static_offset:
        out = _flash(qg, k, v, jnp.zeros((), jnp.float32), kind, window,
                     int(q_offset), chunk_q, chunk_k)
    else:
        out = _flash(qg, k, v, jnp.asarray(q_offset, jnp.float32), kind,
                     window, None, chunk_q, chunk_k)
    # (b, kv, g, sq, hd) -> (b, sq, h, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)


def context_parallel_attention(q, k, v, mesh, cp_axis: str, *, kind: str,
                               window: int, chunk_q: int = 512,
                               chunk_k: int = 512):
    """Sequence-sharded attention via shard_map (heads not divisible by TP)."""
    b, s, h, hd = q.shape
    axis_size = mesh.shape[cp_axis]
    s_local = s // axis_size
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = P(dp, cp_axis, None, None)

    def local_fn(q_l, k_l, v_l):
        idx = jax.lax.axis_index(cp_axis)
        k_all = jax.lax.all_gather(k_l, cp_axis, axis=1, tiled=True)
        v_all = jax.lax.all_gather(v_l, cp_axis, axis=1, tiled=True)
        return chunked_attention(
            q_l, k_all, v_all, kind=kind, window=window,
            q_offset=idx * s_local, chunk_q=min(chunk_q, s_local),
            chunk_k=chunk_k, static_offset=False)

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def decode_attention(q, k_cache, v_cache, kv_len, *, kind: str = "causal",
                     window: int = 0, backend: str = "jax",
                     interpret: Optional[bool] = None):
    """Single-token attention. q (b, 1, h, hd); caches (b, S, kv, hd).

    ``kv_len`` is a scalar (whole-batch cache length) or a (b,) vector of
    per-slot lengths — continuous batching decodes requests of mixed age in
    one step, each slot masking its own valid prefix.

    ``backend="pallas"`` routes through ``kernels/flash_decode.py`` (grid
    over slot x kv-head, online-softmax KV streaming); this path is the
    serving decode oracle-match, valid for fixed-slot and ring caches alike
    (paged pools dispatch directly to ``flash_decode_paged`` upstream).
    """
    if backend == "pallas":
        from repro.kernels.flash_decode import flash_decode
        return flash_decode(q, k_cache, v_cache, kv_len,
                            interpret=interpret)
    b, _, h, hd = q.shape
    _, S, kvh, _ = k_cache.shape
    g = h // kvh
    scale = hd ** -0.5
    qh = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    lens = jnp.reshape(jnp.asarray(kv_len), (-1, 1))     # (1,1) or (b,1)
    valid = jnp.arange(S)[None, :] < lens                # (1,S) or (b,S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, 1, h, hd)
