"""Pallas TPU kernel family: single-token flash-decode over slot caches.

Single-token decode over the continuous-batching caches is the serving hot
path.  This kernel family reads the repo's cache layouts *directly*:

* **contiguous** (`flash_decode`) — fixed-slot `(b, S, kv, hd)` K/V rows and
  SWA ring buffers share one kernel: the ring's scrambled storage order is
  harmless (RoPE is applied at write time, so decode attention is a pure
  set-reduction over valid entries) and per-slot `kv_len` masking handles
  both the mixed-age fixed case (`kv_len = pos+1`) and the wrapped ring
  (`kv_len = S` once `pos >= S`).
* **paged** (`flash_decode_paged`) — page pools `(rows, page, kv, hd)`
  behind per-slot int32 block tables: the block table is scalar-prefetched
  and each grid step's K/V block index map resolves `pool[bt[slot, page]]`,
  so the materialised contiguous gather (`pool[bt].reshape(...)`) in
  `models/decode.py::_block_decode` disappears from the paged hot path.
  Scratch-page-evicted slots ride the batch safely: their reads are
  kv_len-masked exactly like the jnp path.

Layout.  K/V are viewed as `(…, S, kv*hd)` (a free reshape), so every block
keeps whole rows in its minor dim and the TPU's (8, 128) tiling never splits
a head.  The grouped-query tile is laid out block-diagonally: row
`(head, j)` of the `(kv*g, kv*hd)` query holds that head's query in its own
head's lanes and zeros elsewhere, so one matmul against a `(bk, kv*hd)` key
block gives every head's scores, and `p @ v` leaves each row's answer in its
own head's lanes, which the wrapper picks out.

Grid is (slot, key block); the key-block axis carries the online-softmax
`(m, l, acc)` state in VMEM scratch and writes the output on its last step.
Blocks past a slot's `kv_len` skip their compute, and their index map
repeats the last valid block so no new DMA is issued.  Softmax statistics
accumulate in fp32 regardless of cache dtype, matching `decode_attention`'s
`preferred_element_type` discipline (tests/test_kernels_decode.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
DEFAULT_BK = 128


def _decode_kernel(bt_ref, kvl_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
                   acc_sc, *, bk: int, scale: float):
    """q_ref (1, R, kv*hd) block-diagonal query rows; k/v_ref (1, bk, kv*hd)
    one key block; o_ref (1, R, kv*hd).  Grid (slot, key block)."""
    del bt_ref                       # consumed by the index maps only
    slot, c = pl.program_id(0), pl.program_id(1)
    kv_len = kvl_ref[slot]

    @pl.when(c == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(c * bk < kv_len)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale                 # (R, L)
        k = k_ref[0].astype(jnp.float32)                         # (bk, L)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        kpos = c * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


def _norm_kv_len(kv_len, b: int):
    """Scalar (lockstep / cross-attn) or (b,) per-slot lengths -> (b,) i32."""
    kvl = jnp.reshape(jnp.asarray(kv_len, jnp.int32), (-1,))
    return jnp.broadcast_to(kvl, (b,))


def _diag_queries(q, kvh: int):
    """q (b, 1, h, hd) -> (b, R, kvh*hd): row (head, j) holds q[head, j] in
    head's lanes, zero elsewhere; R pads kvh*g up to a sublane multiple."""
    b, _, h, hd = q.shape
    g = h // kvh
    qh = q.reshape(b, kvh, g, 1, hd)
    eye = jnp.eye(kvh, dtype=q.dtype).reshape(1, kvh, 1, kvh, 1)
    qd = (qh * eye).reshape(b, h, kvh * hd)
    return jnp.pad(qd, ((0, 0), (0, (-h) % 8), (0, 0)))


def _take_diag(o, q_shape, kvh: int):
    """Inverse of `_diag_queries` on the kernel output -> (b, 1, h, hd)."""
    b, _, h, hd = q_shape
    g = h // kvh
    o = o[:, :h].reshape(b, kvh, g, kvh, hd)
    o = jnp.einsum("bkgkd->bkgd", o)
    return o.reshape(b, 1, h, hd)


def _call(q, k, v, bt, kvl, *, bk: int, nblk: int, kv_block, interpret):
    """Shared pallas_call: k/v (rows, bk, L) blocks picked by ``kv_block``
    (slot, c, bt_ref, kvl_ref) -> block row; bt is scalar-prefetched."""
    b, _, h, hd = q.shape
    L = k.shape[-1]
    kvh = L // hd
    qd = _diag_queries(q, kvh)
    R = qd.shape[1]

    def kv_map(s, c, bt_ref, kvl_ref):
        # past kv_len: repeat the last valid block (no new DMA, compute off)
        last = jnp.maximum((kvl_ref[s] - 1) // bk, 0)
        return (kv_block(s, jnp.minimum(c, last), bt_ref), 0, 0)

    kernel = functools.partial(_decode_kernel, bk=bk, scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nblk),
            in_specs=[pl.BlockSpec((1, R, L), lambda s, c, *_: (s, 0, 0)),
                      pl.BlockSpec((1, bk, L), kv_map),
                      pl.BlockSpec((1, bk, L), kv_map)],
            out_specs=pl.BlockSpec((1, R, L), lambda s, c, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, L), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, R, L), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(bt, kvl, qd, k, v)
    return _take_diag(out, q.shape, kvh)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q, k_cache, v_cache, kv_len, *, bk: int = DEFAULT_BK,
                 interpret: bool = None):
    """Single-token decode attention over a contiguous slot cache.

    q (b, 1, h, hd); k/v_cache (b, S, kv, hd) — fixed-slot rows or SWA ring
    buffers (storage order is irrelevant post-RoPE); kv_len scalar or (b,)
    per-slot valid lengths.  Returns (b, 1, h, hd), matching
    ``models.attention.decode_attention`` to float tolerance.
    """
    b = q.shape[0]
    _, S, kvh, hd = k_cache.shape
    bk = math.gcd(S, min(bk, S))
    if bk % 8:                       # TPU tiling: sublane multiple or all of S
        bk = S
    nblk = S // bk
    k = k_cache.reshape(b * nblk, bk, kvh * hd)
    v = v_cache.reshape(b * nblk, bk, kvh * hd)
    dummy_bt = jnp.zeros((1,), jnp.int32)
    return _call(q, k, v, dummy_bt, _norm_kv_len(kv_len, b), bk=bk,
                 nblk=nblk, kv_block=lambda s, c, _: s * nblk + c,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q, k_pool, v_pool, bt, kv_len, *,
                       interpret: bool = None):
    """Single-token decode attention over a paged pool via block-table
    indirection — no materialised contiguous gather.

    q (b, 1, h, hd); k/v_pool (rows, page, kv, hd); bt (b, ncols) int32
    mapping each slot's logical pages to pool rows; kv_len scalar or (b,).
    Equivalent to gathering ``pool[bt].reshape(b, ncols*page, kv, hd)`` and
    calling ``decode_attention`` — to float tolerance, minus the copy.
    """
    b = q.shape[0]
    rows, pg, kvh, hd = k_pool.shape
    ncols = bt.shape[-1]
    k = k_pool.reshape(rows, pg, kvh * hd)
    v = v_pool.reshape(rows, pg, kvh * hd)
    return _call(q, k, v, bt.astype(jnp.int32).reshape(-1),
                 _norm_kv_len(kv_len, b), bk=pg, nblk=ncols,
                 kv_block=lambda s, c, bt_ref: bt_ref[s * ncols + c],
                 interpret=interpret)
